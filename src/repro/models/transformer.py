"""Decoder-only transformer family (dense GQA + MoE variants + VLM backbone).

Covers qwen3-4b (qk_norm), qwen2.5-14b / qwen1.5-32b (QKV bias), yi-9b,
internvl2-26b (vision-prefix backbone; the ViT frontend is a stub per the
assignment — ``vision_embeds`` arrive precomputed), granite-moe and olmoe
(MoE MLPs). Layers are stacked on a leading axis and traversed with
jax.lax.scan so the HLO stays compact for the 512-device dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime import compat
from .layers import (MoEConfig, apply_rope, attention, decode_attention,
                     gather_seq, moe_layer, paged_decode_attention,
                     quantize_kv, rms_norm, shard_seq, swiglu)

# Serving-engine capability flags (see configs/base.py and serving/engine.py):
# prefill accepts ``true_lengths`` for length-bucketed padded prompts, the
# KV cache pages cleanly (pure attention KV, per-position writes), and the
# pooled-cache slot layout is declared instead of assumed.
PREFILL_TRUE_LENGTHS = True
SUPPORTS_PAGED_KV = True
CACHE_BATCH_AXES = {"k": 1, "v": 1, "k_scale": 1, "v_scale": 1, "length": 0}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    moe: MoEConfig | None = None
    window: int | None = None         # sliding-window attention (None = full)
    remat: bool = True                # per-layer activation checkpointing
    vision_tokens: int = 0            # VLM prefix length (stub frontend)
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"           # auto | xla | pallas (flash policy)
    ring_attn: str | None = None      # context-parallel mode override
    #   (auto|ring|replicated|off); None defers to configs.base policy /
    #   REPRO_RING_ATTN — see RingAttnPolicy

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def param_count(self) -> int:
        D, H, Kv, Dh, F, V, L = (self.d_model, self.n_heads, self.n_kv_heads,
                                 self.dh, self.d_ff, self.vocab, self.n_layers)
        attn = D * H * Dh + 2 * D * Kv * Dh + H * Dh * D
        if self.moe:
            mlp = D * self.moe.n_experts + \
                3 * self.moe.n_experts * D * self.moe.d_ff
        else:
            mlp = 3 * D * F
        return L * (attn + mlp + 2 * D) + 2 * V * D + D

    def active_param_count(self) -> int:
        """Per-token active params (MoE uses top_k experts)."""
        if not self.moe:
            return self.param_count()
        D, H, Kv, Dh, L = (self.d_model, self.n_heads, self.n_kv_heads,
                           self.dh, self.n_layers)
        attn = D * H * Dh + 2 * D * Kv * Dh + H * Dh * D
        mlp = D * self.moe.n_experts + 3 * self.moe.top_k * D * self.moe.d_ff
        return L * (attn + mlp + 2 * D) + 2 * self.vocab * D + D


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    D, H, Kv, Dh, F, V, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh,
                             cfg.d_ff, cfg.vocab, cfg.n_layers)
    ks = jax.random.split(key, 16)
    dt = cfg.dtype
    s = 0.02

    def nrm(k, shape, scale=s):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    layers: dict[str, jax.Array] = {
        "ln1": jnp.ones((L, D), dt),
        "ln2": jnp.ones((L, D), dt),
        "wq": nrm(ks[0], (L, D, H * Dh)),
        "wk": nrm(ks[1], (L, D, Kv * Dh)),
        "wv": nrm(ks[2], (L, D, Kv * Dh)),
        "wo": nrm(ks[3], (L, H * Dh, D)),
    }
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * Dh), dt)
        layers["bk"] = jnp.zeros((L, Kv * Dh), dt)
        layers["bv"] = jnp.zeros((L, Kv * Dh), dt)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Dh), dt)
        layers["k_norm"] = jnp.ones((L, Dh), dt)
    if cfg.moe:
        E, Fe = cfg.moe.n_experts, cfg.moe.d_ff
        layers["router"] = nrm(ks[4], (L, D, E))
        layers["w_gate"] = nrm(ks[5], (L, E, D, Fe))
        layers["w_up"] = nrm(ks[6], (L, E, D, Fe))
        layers["w_down"] = nrm(ks[7], (L, E, Fe, D))
    else:
        layers["w_gate"] = nrm(ks[5], (L, D, F))
        layers["w_up"] = nrm(ks[6], (L, D, F))
        layers["w_down"] = nrm(ks[7], (L, F, D))
    return {
        "embed": nrm(ks[8], (V, D)),
        "layers": layers,
        "ln_f": jnp.ones((D,), dt),
        "lm_head": nrm(ks[9], (D, V)),
    }


def _qkv(cfg: TransformerConfig, lp: dict, x: jax.Array, positions):
    B, S, D = x.shape
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Kv, Dh)
    v = v.reshape(B, S, Kv, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_train(cfg: TransformerConfig, x, lp, positions):
    """One training layer under the named scopes ``qkv``, ``attention``
    (attention and the output projection) and ``mlp``, as in
    ``paged_step``."""
    with jax.named_scope("qkv"):
        h = gather_seq(rms_norm(x, lp["ln1"], cfg.norm_eps))
        q, k, v = _qkv(cfg, lp, h, positions)
    with jax.named_scope("attention"):
        o = attention(q, k, v, causal=True, window=cfg.window,
                      impl=cfg.attn_impl, ring=cfg.ring_attn)
        # saved by the remat policy: backward reuses the attention output
        # instead of re-streaming the whole flash pipeline (§Perf B1)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(o, "attn_out")
        # Megatron-SP residual stream: the carry x stays SEQUENCE-SHARDED
        # and only the deltas are resharded before the add — GSPMD then
        # lowers the wo / w_down partial-sum contractions as reduce-scatter
        # instead of all-reduce (16x fewer collective bytes; §Perf B2).
        x = x + shard_seq(o.reshape(*x.shape[:2], -1) @ lp["wo"])
    with jax.named_scope("mlp"):
        h = gather_seq(rms_norm(x, lp["ln2"], cfg.norm_eps))
        if cfg.moe:
            mo, aux = moe_layer(h, lp, cfg.moe)
        else:
            mo, aux = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), 0.0
        return x + shard_seq(mo), aux


def forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            vision_embeds: jax.Array | None = None):
    """tokens: (B, S_text) int32 -> logits (B, S, vocab), aux_loss.

    For VLM configs, ``vision_embeds`` (B, P, D) is prepended (stub ViT).
    Each layer runs under the scopes of ``_block_train``; the final norm
    and the output head under ``lm_head``."""
    x = params["embed"][tokens]
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].astype(jnp.int32)

    def body(carry, lp):
        x, aux = carry
        x, a = _block_train(cfg, x, lp, positions)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"))
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["layers"])
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = x @ params["lm_head"]
    return logits, aux


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               kv_dtype: Any = None) -> dict:
    kv_dtype = kv_dtype or cfg.dtype
    L, Kv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.dh
    cache = {
        "k": jnp.zeros((L, batch, max_len, Kv, Dh), kv_dtype),
        "v": jnp.zeros((L, batch, max_len, Kv, Dh), kv_dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if kv_dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros((L, batch, max_len, Kv), jnp.float32)
        cache["v_scale"] = jnp.zeros((L, batch, max_len, Kv), jnp.float32)
    return cache


def prefill(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            cache: dict, vision_embeds: jax.Array | None = None,
            true_lengths: jax.Array | None = None):
    """Run the prompt through the model, filling the cache.

    Returns (logits_last, cache).

    ``true_lengths`` (B,) supports length-BUCKETED prompts: tokens may be
    right-padded to a bucket size, and causality guarantees every position
    < true_lengths[b] is unaffected by the padding.  The cache length is
    set to the true length (decode overwrites the first junk position and
    masks the rest) and the returned logits are taken at position
    ``true_lengths - 1`` instead of the padded last row."""
    x = params["embed"][tokens]
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].astype(jnp.int32)

    def body(x, lp):
        h = gather_seq(rms_norm(x, lp["ln1"], cfg.norm_eps))
        q, k, v = _qkv(cfg, lp, h, positions)
        o = attention(q, k, v, causal=True, window=cfg.window,
                      impl=cfg.attn_impl, ring=cfg.ring_attn)
        x = x + o.reshape(B, S, -1) @ lp["wo"]
        h = gather_seq(rms_norm(x, lp["ln2"], cfg.norm_eps))
        if cfg.moe:
            mo, _ = moe_layer(h, lp, cfg.moe)
        else:
            mo = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return shard_seq(x + mo), (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    kv_dt = cache["k"].dtype
    if true_lengths is None:
        new_cache = {"length": jnp.full((B,), S, jnp.int32)}
    else:
        new_cache = {"length": true_lengths.astype(jnp.int32)}
    if kv_dt == jnp.int8:
        kq, kscale = quantize_kv(ks)
        vq, vscale = quantize_kv(vs)
        new_cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], kq, (0, 0, 0, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vq, (0, 0, 0, 0, 0))
        new_cache["k_scale"] = jax.lax.dynamic_update_slice(
            cache["k_scale"], kscale, (0, 0, 0, 0))
        new_cache["v_scale"] = jax.lax.dynamic_update_slice(
            cache["v_scale"], vscale, (0, 0, 0, 0))
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(kv_dt), (0, 0, 0, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(kv_dt), (0, 0, 0, 0, 0))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if true_lengths is None:
        logits = x[:, -1:] @ params["lm_head"]
    else:
        last = x[jnp.arange(B), true_lengths - 1][:, None]
        logits = last @ params["lm_head"]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged KV serving (block-pool cache; see repro.serving.kv)
# ---------------------------------------------------------------------------

def init_paged_pool(cfg: TransformerConfig, num_pages: int, page_size: int,
                    kv_dtype: Any = None) -> dict:
    """Global page-pool arrays for the paged serving path.  Page 0 is the
    TRASH page (pad-token writes land there; never mapped to a slot)."""
    kv_dtype = kv_dtype or cfg.dtype
    L, Kv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.dh
    pool = {
        "k": jnp.zeros((L, num_pages, page_size, Kv, Dh), kv_dtype),
        "v": jnp.zeros((L, num_pages, page_size, Kv, Dh), kv_dtype),
    }
    if kv_dtype == jnp.int8:
        pool["k_scale"] = jnp.zeros((L, num_pages, page_size, Kv),
                                    jnp.float32)
        pool["v_scale"] = jnp.zeros((L, num_pages, page_size, Kv),
                                    jnp.float32)
    return pool


def paged_step(cfg: TransformerConfig, params: dict, tokens: jax.Array,
               pool: dict, page_table: jax.Array, lengths: jax.Array,
               counts: jax.Array):
    """One paged serving step: scatter T new tokens' K/V into the pool and
    attend against each slot's paged history.

    tokens: (B, T); counts: (B,) valid tokens per row (<= T; rows with
    count 0 are idle slots riding the SPMD step).  Rows are INDEPENDENT,
    so one call may mix prefill chunks (counts[b] > 1) and decode rows
    (counts[b] == 1) — the engine's continuous-batching tick is exactly
    such a merged call.  page_table: (B, max_pages_view) physical page
    ids — the engine passes a power-of-two SLICE of the full table
    covering the longest active slot, so gather/attention cost scales
    with actual lengths, not max_len.  lengths: (B,) tokens cached before
    this call; because positions derive from it, a row whose leading
    pages were mapped read-only from the prefix cache simply starts with
    lengths[b] == matched tokens and writes land mid-sequence (mid-page
    included) in its first PRIVATE page — shared pages are never written.
    Pad/idle writes are routed to trash page 0.

    Returns (logits (B, T, vocab), pool', lengths + counts).  The layer's
    parts run under the named scopes ``qkv``, ``attention`` (pool write,
    paged attention, output projection), ``mlp`` and ``lm_head``, which a
    profiler trace reports with each device op."""
    x = params["embed"][tokens]
    B, T, _ = x.shape
    page = pool["k"].shape[2]
    MP = page_table.shape[1]
    positions = lengths[:, None] + jnp.arange(T)[None, :]      # (B, T)
    valid = jnp.arange(T)[None, :] < counts[:, None]
    lp_idx = jnp.clip(positions // page, 0, MP - 1)
    phys = jnp.where(valid,
                     jnp.take_along_axis(page_table, lp_idx, axis=1), 0)
    off = positions % page
    quantized = "k_scale" in pool
    # the Pallas kernel path is decode-only; chunked prefill stays on the
    # gather path (its q block is the whole chunk, a different schedule)
    impl = cfg.attn_impl if T == 1 else "xla"

    def replicate(x):
        # Pin per-token tensors REPLICATED whenever a mesh is ambient.
        # With a head-dim-sharded pool, letting GSPMD propagate the
        # scatter operand's sharding back INTO the rope/qk-norm subgraph
        # miscompiles on the 0.4.37 CPU partitioner (measured: q off by
        # >2x, written pages doubled — rope's split/concat on the sharded
        # Dh axis feeding a scatter is the trigger).  Serving tokens are
        # a few KB, so replicating them is free; the POOL stays sharded
        # and the gather/attention path handles it exactly.  No-op
        # outside a mesh context.
        mesh = compat.get_abstract_mesh()
        if mesh is None or getattr(mesh, "empty", False):
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*([None] * x.ndim)))

    def write(pages, l, new):
        # (L, P, page, ...) scattered at per-token (l, phys, off) in place:
        # rows of one slot never collide (consecutive positions), distinct
        # slots own distinct pages, and all invalid tokens land on trash
        # page 0.
        return pages.at[l, phys, off].set(new.astype(pages.dtype))

    def body(carry, inp):
        x, pool = carry
        lp, l = inp
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lp, h, positions)
            q, k, v = replicate(q), replicate(k), replicate(v)
        with jax.named_scope("attention"):
            if quantized:
                (kq, ks_), (vq, vs_) = quantize_kv(k), quantize_kv(v)
                new = {"k": kq, "v": vq, "k_scale": ks_, "v_scale": vs_}
            else:
                new = {"k": k, "v": v}
            pool = {n: write(pool[n], l, new[n]) for n in pool}
            o = paged_decode_attention(
                q, pool["k"], pool["v"], page_table, lengths,
                pool.get("k_scale"), pool.get("v_scale"), layer=l,
                impl=impl)
            x = x + replicate(o).reshape(B, T, -1) @ lp["wo"]
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.moe:
                mo, _ = moe_layer(h, lp, cfg.moe)
            else:
                mo = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        # the residual carry stays replicated too: serving activations are
        # small, and this keeps GSPMD from threading pool-derived layouts
        # through the layer scan
        return (replicate(x + mo), pool), None

    # the pool rides in the carry, not in xs/ys: each layer updates the
    # stacked (L, P, ...) arrays in place and the kernel reads layer l
    # through its index maps, so no layer's pool is sliced out or written
    # back (with the pool donated, the step never copies it)
    L = pool["k"].shape[0]
    (x, new_pool), _ = jax.lax.scan(
        body, (x, pool), (params["layers"], jnp.arange(L)))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = x @ params["lm_head"]
    return logits, new_pool, lengths + counts


def decode_step(cfg: TransformerConfig, params: dict, tokens: jax.Array,
                cache: dict):
    """tokens: (B, 1) -> (logits (B, 1, V), cache). One serving step."""
    x = params["embed"][tokens]
    B = x.shape[0]
    positions = cache["length"][:, None].astype(jnp.int32)

    quantized = "k_scale" in cache

    def upd_cache(c, new):
        # per-slot write position (continuous batching: lengths differ)
        return jax.vmap(
            lambda cb, nb, p: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (p,) + (0,) * (cb.ndim - 1))
        )(c, new, cache["length"])

    def body(x, inp):
        if quantized:
            lp, kc, vc, ksc, vsc = inp
        else:
            lp, kc, vc = inp
            ksc = vsc = None
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, lp, h, positions)
        if quantized:
            kq, ks_ = quantize_kv(k)
            vq, vs_ = quantize_kv(v)
            kc, vc = upd_cache(kc, kq), upd_cache(vc, vq)
            ksc, vsc = upd_cache(ksc, ks_), upd_cache(vsc, vs_)
            o = decode_attention(q, kc, vc, cache["length"] + 1, ksc, vsc)
            out_caches = (kc, vc, ksc, vsc)
        else:
            kc, vc = upd_cache(kc, k), upd_cache(vc, v)
            o = decode_attention(q, kc, vc, cache["length"] + 1)
            out_caches = (kc, vc)
        x = x + o.reshape(B, 1, -1) @ lp["wo"]
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe:
            mo, _ = moe_layer(h, lp, cfg.moe)
        else:
            mo = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + mo, out_caches

    if quantized:
        x, (ks, vs, kss, vss) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        new_cache = {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss,
                     "length": cache["length"] + 1}
    else:
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                             cache["v"]))
        new_cache = {"k": ks, "v": vs, "length": cache["length"] + 1}
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    return logits, new_cache

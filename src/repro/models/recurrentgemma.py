"""RecurrentGemma / Griffin (arXiv:2402.19427): RG-LRU + local attention, 1:2.

Block pattern repeats (recurrent, recurrent, local-attention); 38 layers =
12 full groups + 2 trailing recurrent blocks. The RG-LRU is a gated linear
recurrence evaluated with an associative scan (train/prefill) or a single
state update (decode) — sub-quadratic in sequence length. Local
attention is MQA (kv=1) with a 2048 sliding window, so its decode cache is
window-bounded, not seq_len-bounded.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .layers import (apply_rope, attention, decode_attention, gather_seq,
                     geglu, rms_norm, shard_seq)

RG_LRU_C = 8.0

# Pooled-serving slot layout (see serving/engine.py _write_slot).  NOTE the
# grouped recurrent states carry batch at axis 2 — (G, 2, batch, ...) — which
# the seed engine's fixed axis-1 assumption silently corrupted; declaring the
# axes here is what makes pooled slot writes correct for this family.
CACHE_BATCH_AXES = {"conv_g": 2, "lru_g": 2, "k": 1, "v": 1,
                    "conv_t": 1, "lru_t": 1, "length": 0}


@dataclasses.dataclass(frozen=True)
class RGConfig:
    name: str
    n_layers: int                  # total blocks (38)
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    window: int = 2048
    conv_width: int = 4
    remat: bool = True
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"           # auto | xla | pallas (flash policy)

    @property
    def dh(self) -> int:
        return self.d_model // self.n_heads

    @property
    def lru_width(self) -> int:
        return self.d_model

    @property
    def n_groups(self) -> int:
        return self.n_layers // 3

    @property
    def n_tail_rec(self) -> int:
        return self.n_layers - 3 * self.n_groups

    def param_count(self) -> int:
        D, W, F = self.d_model, self.lru_width, self.d_ff
        H, Kv, Dh = self.n_heads, self.n_kv_heads, self.dh
        rec = 2 * D * W + self.conv_width * W + 2 * W * W + W + W * D + 2 * D
        attn = D * H * Dh + 2 * D * Kv * Dh + H * Dh * D + 2 * D
        mlp = 3 * D * F
        n_rec = 2 * self.n_groups + self.n_tail_rec
        n_attn = self.n_groups
        return (n_rec * (rec + mlp) + n_attn * (attn + mlp)
                + 2 * self.vocab * D + D)


def _init_rec(cfg: RGConfig, key, n: int, dt):
    D, W = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 8)

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "ln1": jnp.ones((n, D), dt),
        "ln2": jnp.ones((n, D), dt),
        "w_x": nrm(ks[0], (n, D, W)),          # branch into conv + LRU
        "w_y": nrm(ks[1], (n, D, W)),          # gate branch (GeLU)
        "conv_w": nrm(ks[2], (n, cfg.conv_width, W), 0.2),
        "w_a": nrm(ks[3], (n, W, W)),          # recurrence gate
        "w_i": nrm(ks[4], (n, W, W)),          # input gate
        "lam": jnp.full((n, W), 2.0, jnp.float32),   # Lambda (pre-softplus)
        "w_out": nrm(ks[5], (n, W, D)),
        "mlp_gate": nrm(ks[6], (n, D, cfg.d_ff)),
        "mlp_up": nrm(ks[7], (n, D, cfg.d_ff)),
        "mlp_down": nrm(jax.random.fold_in(key, 99), (n, cfg.d_ff, D)),
    }


def _init_attn(cfg: RGConfig, key, n: int, dt):
    D, H, Kv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    ks = jax.random.split(key, 8)

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    return {
        "ln1": jnp.ones((n, D), dt),
        "ln2": jnp.ones((n, D), dt),
        "wq": nrm(ks[0], (n, D, H * Dh)),
        "wk": nrm(ks[1], (n, D, Kv * Dh)),
        "wv": nrm(ks[2], (n, D, Kv * Dh)),
        "wo": nrm(ks[3], (n, H * Dh, D)),
        "mlp_gate": nrm(ks[4], (n, D, cfg.d_ff)),
        "mlp_up": nrm(ks[5], (n, D, cfg.d_ff)),
        "mlp_down": nrm(ks[6], (n, cfg.d_ff, D)),
    }


def init_params(cfg: RGConfig, key: jax.Array) -> dict:
    dt = cfg.dtype
    ks = jax.random.split(key, 6)
    G, Tr = cfg.n_groups, cfg.n_tail_rec
    rec = _init_rec(cfg, ks[0], 2 * G, dt)
    rec_groups = jax.tree.map(
        lambda a: a.reshape((G, 2) + a.shape[1:]), rec)
    params = {
        "embed": (jax.random.normal(ks[1], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "rec_groups": rec_groups,
        "attn_groups": _init_attn(cfg, ks[2], G, dt),
        "rec_tail": _init_rec(cfg, ks[3], Tr, dt) if Tr else None,
        "ln_f": jnp.ones((cfg.d_model,), dt),
        "lm_head": (jax.random.normal(ks[4], (cfg.d_model, cfg.vocab),
                                      jnp.float32) * 0.02).astype(dt),
    }
    return params


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def _rg_lru_scan(x, r, i, lam):
    """x, r, i: (B, L, W); lam: (W,). h_t = a_t h_{t-1} + sqrt(1-a_t^2) i x."""
    log_a = -RG_LRU_C * jax.nn.softplus(lam) * r          # (B, L, W)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - a * a, 0.0)) * (i * x)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    return h


def _rec_mixer(cfg: RGConfig, lp, x, conv_state=None, lru_state=None,
               single_step=False):
    """Griffin recurrent block mixer. x: (B, L, D)."""
    B, L, D = x.shape
    W = cfg.lru_width
    u = x @ lp["w_x"]                                  # (B, L, W)
    gate = jax.nn.gelu((x @ lp["w_y"]).astype(jnp.float32))
    conv_w = lp["conv_w"].astype(jnp.float32)          # (cw, W)

    if single_step:
        win = jnp.concatenate([conv_state, u.astype(jnp.float32)], axis=1)
        new_conv = win[:, 1:]
        u = (win * conv_w[None]).sum(1)[:, None]       # (B, 1, W)
    else:
        pad = jnp.zeros((B, cfg.conv_width - 1, W), jnp.float32)
        seq = jnp.concatenate([pad, u.astype(jnp.float32)], axis=1)
        u = sum(seq[:, j:j + L] * conv_w[j][None, None]
                for j in range(cfg.conv_width))
        new_conv = seq[:, L:]

    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("blw,wv->blv", uf,
                                  lp["w_a"].astype(jnp.float32)))
    ig = jax.nn.sigmoid(jnp.einsum("blw,wv->blv", uf,
                                   lp["w_i"].astype(jnp.float32)))
    lam = lp["lam"].astype(jnp.float32)

    if single_step:
        log_a = -RG_LRU_C * jax.nn.softplus(lam) * r[:, 0]
        a = jnp.exp(log_a)
        h = a * lru_state + jnp.sqrt(jnp.clip(1 - a * a, 0.0)) * \
            (ig[:, 0] * uf[:, 0])
        new_lru = h
        h = h[:, None]
    else:
        h = _rg_lru_scan(uf, r, ig, lam)
        new_lru = h[:, -1]

    out = (h * gate).astype(cfg.dtype) @ lp["w_out"]
    return out, new_conv, new_lru


def _attn_mixer(cfg: RGConfig, lp, x, positions, kc=None, vc=None,
                lengths=None, single_step=False):
    B, L, D = x.shape
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = (x @ lp["wq"]).reshape(B, L, H, Dh)
    k = (x @ lp["wk"]).reshape(B, L, Kv, Dh)
    v = (x @ lp["wv"]).reshape(B, L, Kv, Dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if single_step:
        slots = lengths % kc.shape[1]                  # per-slot ring write
        upd = jax.vmap(lambda cb, nb, p: jax.lax.dynamic_update_slice(
            cb, nb.astype(cb.dtype), (p, 0, 0)))
        kc = upd(kc, k, slots)
        vc = upd(vc, v, slots)
        o = decode_attention(q, kc, vc,
                             jnp.minimum(lengths + 1, kc.shape[1]))
    else:
        o = attention(q, k, v, causal=True, window=cfg.window,
                      impl=cfg.attn_impl)
    out = o.reshape(B, L, H * Dh) @ lp["wo"]
    return out, kc, vc


def _rec_block(cfg, lp, x, *args, **kw):
    h = gather_seq(rms_norm(x, lp["ln1"], cfg.norm_eps))
    o, conv_s, lru_s = _rec_mixer(cfg, lp, h, *args, **kw)
    x = x + o
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
    return x, conv_s, lru_s


def _attn_block(cfg, lp, x, positions, **kw):
    h = gather_seq(rms_norm(x, lp["ln1"], cfg.norm_eps))
    o, kc, vc = _attn_mixer(cfg, lp, h, positions, **kw)
    x = x + o
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
    return x, kc, vc


def forward(cfg: RGConfig, params: dict, tokens: jax.Array,
            vision_embeds=None):
    x = params["embed"][tokens]
    B, L = tokens.shape
    positions = jnp.arange(L)[None, :].astype(jnp.int32)

    def group(x, gp):
        rec2, attnp = gp
        for j in range(2):
            lp = jax.tree.map(lambda a: a[j], rec2)
            x, _, _ = _rec_block(cfg, lp, x)
        x, _, _ = _attn_block(cfg, attnp, x, positions)
        return shard_seq(x), None

    def tail(x, lp):
        x, _, _ = _rec_block(cfg, lp, x)
        return shard_seq(x), None

    if cfg.remat:
        group = jax.checkpoint(
            group, policy=jax.checkpoint_policies.nothing_saveable)
        tail = jax.checkpoint(
            tail, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(group, x,
                        (params["rec_groups"], params["attn_groups"]))
    if params["rec_tail"] is not None:
        x, _ = jax.lax.scan(tail, x, params["rec_tail"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x @ params["lm_head"], 0.0


# ---------------------------------------------------------------------------
# Serving: window-bounded attention caches + O(1) recurrent state.
# ---------------------------------------------------------------------------

def init_cache(cfg: RGConfig, batch: int, max_len: int,
               kv_dtype: Any = None) -> dict:
    kv_dtype = kv_dtype or cfg.dtype
    G, Tr, W = cfg.n_groups, cfg.n_tail_rec, cfg.lru_width
    wlen = min(cfg.window, max_len)
    return {
        "conv_g": jnp.zeros((G, 2, batch, cfg.conv_width - 1, W), jnp.float32),
        "lru_g": jnp.zeros((G, 2, batch, W), jnp.float32),
        "k": jnp.zeros((G, batch, wlen, cfg.n_kv_heads, cfg.dh), kv_dtype),
        "v": jnp.zeros((G, batch, wlen, cfg.n_kv_heads, cfg.dh), kv_dtype),
        "conv_t": jnp.zeros((Tr, batch, cfg.conv_width - 1, W), jnp.float32),
        "lru_t": jnp.zeros((Tr, batch, W), jnp.float32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(cfg: RGConfig, params: dict, tokens: jax.Array, cache: dict):
    x = params["embed"][tokens]
    B = x.shape[0]
    positions = cache["length"][:, None].astype(jnp.int32)

    def group(x, inp):
        gp, conv2, lru2, kc, vc = inp
        rec2, attnp = gp
        new_conv, new_lru = [], []
        for j in range(2):
            lp = jax.tree.map(lambda a: a[j], rec2)
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, cs, ls = _rec_mixer(cfg, lp, h, conv2[j], lru2[j],
                                   single_step=True)
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
            new_conv.append(cs)
            new_lru.append(ls)
        x, kc, vc = _attn_block(cfg, attnp, x, positions, kc=kc, vc=vc,
                                lengths=cache["length"], single_step=True)
        return x, (jnp.stack(new_conv), jnp.stack(new_lru), kc, vc)

    x, (convs, lrus, ks, vs) = jax.lax.scan(
        group, x,
        ((params["rec_groups"], params["attn_groups"]),
         cache["conv_g"], cache["lru_g"], cache["k"], cache["v"]))

    conv_t, lru_t = cache["conv_t"], cache["lru_t"]
    if params["rec_tail"] is not None:
        def tail(x, inp):
            lp, cs0, ls0 = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, cs, ls = _rec_mixer(cfg, lp, h, cs0, ls0, single_step=True)
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
            return x, (cs, ls)
        x, (conv_t, lru_t) = jax.lax.scan(
            tail, x, (params["rec_tail"], cache["conv_t"], cache["lru_t"]))

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    new_cache = {"conv_g": convs, "lru_g": lrus, "k": ks, "v": vs,
                 "conv_t": conv_t, "lru_t": lru_t,
                 "length": cache["length"] + 1}
    return logits, new_cache


def prefill(cfg: RGConfig, params: dict, tokens: jax.Array, cache: dict,
            vision_embeds=None):
    """Prefill via forward + state extraction (simplified: recompute final
    states; window cache filled with the last `window` keys)."""
    x = params["embed"][tokens]
    B, L = tokens.shape
    positions = jnp.arange(L)[None, :].astype(jnp.int32)
    wlen = cache["k"].shape[2]

    def group(x, gp):
        rec2, attnp = gp
        convs, lrus = [], []
        for j in range(2):
            lp = jax.tree.map(lambda a: a[j], rec2)
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, cs, ls = _rec_mixer(cfg, lp, h)
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
            convs.append(cs)
            lrus.append(ls)
        h = rms_norm(x, attnp["ln1"], cfg.norm_eps)
        H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
        q = (h @ attnp["wq"]).reshape(B, L, H, Dh)
        k = (h @ attnp["wk"]).reshape(B, L, Kv, Dh)
        v = (h @ attnp["wv"]).reshape(B, L, Kv, Dh)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, k, v, causal=True, window=cfg.window,
                      impl=cfg.attn_impl)
        x = x + o.reshape(B, L, H * Dh) @ attnp["wo"]
        h = rms_norm(x, attnp["ln2"], cfg.norm_eps)
        x = x + geglu(h, attnp["mlp_gate"], attnp["mlp_up"],
                      attnp["mlp_down"])
        # scatter the last `wlen` keys into their ring slots (pos % wlen) so
        # decode_step's ring writes/masks stay consistent.
        take = min(L, wlen)
        slots = (jnp.arange(take) + max(0, L - take)) % wlen
        kw = jnp.zeros((B, wlen) + k.shape[2:], cache["k"].dtype)
        vw = jnp.zeros((B, wlen) + v.shape[2:], cache["v"].dtype)
        kw = kw.at[:, slots].set(k[:, -take:].astype(cache["k"].dtype))
        vw = vw.at[:, slots].set(v[:, -take:].astype(cache["v"].dtype))
        return x, (jnp.stack(convs), jnp.stack(lrus), kw, vw)

    x, (convs, lrus, ks, vs) = jax.lax.scan(
        group, x, (params["rec_groups"], params["attn_groups"]))

    conv_t, lru_t = cache["conv_t"], cache["lru_t"]
    if params["rec_tail"] is not None:
        def tail(x, lp):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, cs, ls = _rec_mixer(cfg, lp, h)
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + geglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
            return x, (cs, ls)
        x, (conv_t, lru_t) = jax.lax.scan(tail, x, params["rec_tail"])

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, -1:] @ params["lm_head"]
    new_cache = {"conv_g": convs, "lru_g": lrus, "k": ks, "v": vs,
                 "conv_t": conv_t, "lru_t": lru_t,
                 "length": jnp.full((B,), L, jnp.int32)}
    return logits, new_cache

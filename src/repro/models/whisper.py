"""Whisper-medium style encoder-decoder (arXiv:2212.04356).

The conv audio frontend is a STUB: the caller supplies precomputed frame
embeddings (B, S_enc, D) directly to the encoder.
Encoder: bidirectional MHA + GELU MLP, sinusoidal positions. Decoder: causal
self-attention + cross-attention over encoder output, learned positions,
tied output embedding. LayerNorm (with bias) throughout, pre-norm.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .layers import (attention, decode_attention, gather_seq, gelu_mlp,
                     layer_norm, shard_seq)

# Pooled-serving slot layout (see serving/engine.py _write_slot): batch axis
# of every cache entry, including the encoder cross-attention K/V.
CACHE_BATCH_AXES = {"k": 1, "v": 1, "xk": 1, "xv": 1, "length": 0}


@dataclasses.dataclass(frozen=True)
class WhisperConfig:
    name: str
    n_layers: int            # per stack (24 enc + 24 dec)
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_audio_ctx: int = 1500
    max_text_ctx: int = 448
    remat: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"           # auto | xla | pallas (flash policy)

    @property
    def dh(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        D, F, L = self.d_model, self.d_ff, self.n_layers
        attn = 4 * D * D
        mlp = 2 * D * F + D + F
        enc = L * (attn + mlp + 4 * D)
        dec = L * (2 * attn + mlp + 6 * D)
        return enc + dec + self.vocab * D + self.max_text_ctx * D + 4 * D


def _sinusoidal(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (dim / (d // 2 - 1)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attn_params(key, n, D, dt):
    ks = jax.random.split(key, 4)

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    return {
        "wq": nrm(ks[0], (n, D, D)), "bq": jnp.zeros((n, D), dt),
        "wk": nrm(ks[1], (n, D, D)),
        "wv": nrm(ks[2], (n, D, D)), "bv": jnp.zeros((n, D), dt),
        "wo": nrm(ks[3], (n, D, D)), "bo": jnp.zeros((n, D), dt),
    }


def init_params(cfg: WhisperConfig, key: jax.Array) -> dict:
    D, F, L, dt = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.dtype
    ks = jax.random.split(key, 12)

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    def ln(n):
        return jnp.ones((n, D), dt), jnp.zeros((n, D), dt)

    enc = {"attn": _attn_params(ks[0], L, D, dt)}
    enc["ln1_w"], enc["ln1_b"] = ln(L)
    enc["ln2_w"], enc["ln2_b"] = ln(L)
    enc["mlp_w1"] = nrm(ks[1], (L, D, F))
    enc["mlp_b1"] = jnp.zeros((L, F), dt)
    enc["mlp_w2"] = nrm(ks[2], (L, F, D))
    enc["mlp_b2"] = jnp.zeros((L, D), dt)

    dec = {"self": _attn_params(ks[3], L, D, dt),
           "cross": _attn_params(ks[4], L, D, dt)}
    dec["ln1_w"], dec["ln1_b"] = ln(L)
    dec["ln2_w"], dec["ln2_b"] = ln(L)
    dec["ln3_w"], dec["ln3_b"] = ln(L)
    dec["mlp_w1"] = nrm(ks[5], (L, D, F))
    dec["mlp_b1"] = jnp.zeros((L, F), dt)
    dec["mlp_w2"] = nrm(ks[6], (L, F, D))
    dec["mlp_b2"] = jnp.zeros((L, D), dt)

    return {
        "embed": nrm(ks[7], (cfg.vocab, D)),
        "pos_dec": nrm(ks[8], (cfg.max_text_ctx, D)),
        "enc": enc,
        "dec": dec,
        "ln_enc_w": jnp.ones((D,), dt), "ln_enc_b": jnp.zeros((D,), dt),
        "ln_dec_w": jnp.ones((D,), dt), "ln_dec_b": jnp.zeros((D,), dt),
    }


def _mha(cfg, lp, xq, xkv, *, causal, impl, prefix=""):
    B, S, D = xq.shape
    H, Dh = cfg.n_heads, cfg.dh
    q = (xq @ lp["wq"] + lp["bq"]).reshape(B, S, H, Dh)
    k = (xkv @ lp["wk"]).reshape(B, xkv.shape[1], H, Dh)
    v = (xkv @ lp["wv"] + lp["bv"]).reshape(B, xkv.shape[1], H, Dh)
    o = attention(q, k, v, causal=causal, window=None, impl=impl)
    return o.reshape(B, S, D) @ lp["wo"] + lp["bo"], k, v


def encode(cfg: WhisperConfig, params: dict, frames: jax.Array) -> jax.Array:
    """frames: (B, S_enc, D) precomputed embeddings (stub frontend)."""
    x = frames.astype(cfg.dtype) + _sinusoidal(
        frames.shape[1], cfg.d_model).astype(cfg.dtype)[None]
    enc = params["enc"]

    def body(x, lp):
        h = gather_seq(layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps))
        o, _, _ = _mha(cfg, lp["attn"], h, h, causal=False,
                       impl=cfg.attn_impl)
        x = x + o
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        x = x + gelu_mlp(h, lp["mlp_w1"], lp["mlp_b1"], lp["mlp_w2"],
                         lp["mlp_b2"])
        return shard_seq(x), None

    stacked = {"attn": enc["attn"],
               **{k: v for k, v in enc.items() if k != "attn"}}
    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, stacked)
    return layer_norm(x, params["ln_enc_w"], params["ln_enc_b"], cfg.norm_eps)


def forward(cfg: WhisperConfig, params: dict, tokens: jax.Array,
            frames: jax.Array):
    """Teacher-forced training step: (tokens (B, S_dec), frames (B, S_enc, D))
    -> logits (B, S_dec, vocab)."""
    enc_out = encode(cfg, params, frames)
    B, S = tokens.shape
    pos = params["pos_dec"]
    pe = pos[jnp.arange(S) % pos.shape[0]]
    x = params["embed"][tokens] + pe[None]
    dec = params["dec"]

    def body(x, lp):
        h = gather_seq(layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps))
        o, _, _ = _mha(cfg, lp["self"], h, h, causal=True,
                       impl=cfg.attn_impl)
        x = x + o
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        o, _, _ = _mha(cfg, lp["cross"], h, enc_out, causal=False,
                       impl=cfg.attn_impl)
        x = x + o
        h = layer_norm(x, lp["ln3_w"], lp["ln3_b"], cfg.norm_eps)
        x = x + gelu_mlp(h, lp["mlp_w1"], lp["mlp_b1"], lp["mlp_w2"],
                         lp["mlp_b2"])
        return shard_seq(x), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, dec)
    x = layer_norm(x, params["ln_dec_w"], params["ln_dec_b"], cfg.norm_eps)
    logits = x @ params["embed"].T          # tied output embedding
    return logits, 0.0


def init_cache(cfg: WhisperConfig, batch: int, max_len: int,
               kv_dtype: Any = None) -> dict:
    kv_dtype = kv_dtype or cfg.dtype
    L, H, Dh = cfg.n_layers, cfg.n_heads, cfg.dh
    return {
        "k": jnp.zeros((L, batch, max_len, H, Dh), kv_dtype),
        "v": jnp.zeros((L, batch, max_len, H, Dh), kv_dtype),
        "xk": jnp.zeros((L, batch, cfg.n_audio_ctx, H, Dh), kv_dtype),
        "xv": jnp.zeros((L, batch, cfg.n_audio_ctx, H, Dh), kv_dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg: WhisperConfig, params: dict, tokens: jax.Array, cache: dict,
            frames: jax.Array):
    """Encode audio, precompute cross K/V, run the prompt through the
    decoder. Returns (last-token logits, cache)."""
    enc_out = encode(cfg, params, frames)
    B, S = tokens.shape
    pe = params["pos_dec"][jnp.arange(S) % params["pos_dec"].shape[0]]
    x = params["embed"][tokens] + pe[None]
    H, Dh = cfg.n_heads, cfg.dh

    def body(x, lp):
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        o, k, v = _mha(cfg, lp["self"], h, h, causal=True,
                       impl=cfg.attn_impl)
        x = x + o
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        o, xk, xv = _mha(cfg, lp["cross"], h, enc_out, causal=False,
                         impl=cfg.attn_impl)
        x = x + o
        h = layer_norm(x, lp["ln3_w"], lp["ln3_b"], cfg.norm_eps)
        x = x + gelu_mlp(h, lp["mlp_w1"], lp["mlp_b1"], lp["mlp_w2"],
                         lp["mlp_b2"])
        return x, (k, v, xk, xv)

    x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["dec"])
    kv_dt = cache["k"].dtype
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(kv_dt), (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(kv_dt), (0, 0, 0, 0, 0)),
        "xk": xks.astype(kv_dt),
        "xv": xvs.astype(kv_dt),
        "length": jnp.full((B,), S, jnp.int32),
    }
    x = layer_norm(x, params["ln_dec_w"], params["ln_dec_b"], cfg.norm_eps)
    return x[:, -1:] @ params["embed"].T, cache


def decode_step(cfg: WhisperConfig, params: dict, tokens: jax.Array,
                cache: dict):
    B = tokens.shape[0]
    pe = params["pos_dec"][cache["length"] % params["pos_dec"].shape[0]]
    x = params["embed"][tokens] + pe[:, None]

    def upd_cache(c, new):
        return jax.vmap(
            lambda cb, nb, p: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (p, 0, 0))
        )(c, new, cache["length"])

    def body(x, inp):
        lp, kc, vc, xk, xv = inp
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        H, Dh = cfg.n_heads, cfg.dh
        q = (h @ lp["self"]["wq"] + lp["self"]["bq"]).reshape(B, 1, H, Dh)
        k = (h @ lp["self"]["wk"]).reshape(B, 1, H, Dh)
        v = (h @ lp["self"]["wv"] + lp["self"]["bv"]).reshape(B, 1, H, Dh)
        kc = upd_cache(kc, k)
        vc = upd_cache(vc, v)
        o = decode_attention(q, kc, vc, cache["length"] + 1)
        x = x + o.reshape(B, 1, -1) @ lp["self"]["wo"] + lp["self"]["bo"]
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        q = (h @ lp["cross"]["wq"] + lp["cross"]["bq"]).reshape(B, 1, H, Dh)
        lens = jnp.full((B,), xk.shape[1], jnp.int32)
        o = decode_attention(q, xk, xv, lens)
        x = x + o.reshape(B, 1, -1) @ lp["cross"]["wo"] + lp["cross"]["bo"]
        h = layer_norm(x, lp["ln3_w"], lp["ln3_b"], cfg.norm_eps)
        x = x + gelu_mlp(h, lp["mlp_w1"], lp["mlp_b1"], lp["mlp_w2"],
                         lp["mlp_b2"])
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["dec"], cache["k"], cache["v"], cache["xk"],
                  cache["xv"]))
    cache = dict(cache, k=ks, v=vs, length=cache["length"] + 1)
    x = layer_norm(x, params["ln_dec_w"], params["ln_dec_b"], cfg.norm_eps)
    return x @ params["embed"].T, cache

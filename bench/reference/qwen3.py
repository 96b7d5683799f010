"""Plain float32 Qwen3 forward pass (arXiv:2505.09388; the published
``Qwen3ForCausalLM``), the yardstick for served tokens.

Per layer: x += Wo attn(rope(qnorm(Wq h)), rope(knorm(Wk h)), Wv h) with
h = rmsnorm(x) * g1, causal grouped-query softmax attention at scale
1/sqrt(head_dim); then x += Wd (silu(Wg h2) * Wu h2) with h2 =
rmsnorm(x) * g2.  Logits = (rmsnorm(x) * gf) Wout.  RoPE rotates the two
halves of each head (theta from the config).  Departure, shared with the
program and stated in the configuration file: the output head is its own
matrix, not the tied embedding.

It imports nothing of the program.  Weights come in the program's layout
(a dict of stacked per-layer arrays) only because that is how the
benchmark made them; each layer is upcast to float32 on its own, so the
whole model never sits in float32 at once.  Matrix products run at
``highest`` precision.

``quant="fp8"`` is the control: every matrix product takes both operands
through float8 e4m3 with one scale per tensor (per row for activations),
as a lower-precision path would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512           # query rows per attention block (bounds memory)
E4M3_MAX = 448.0


def _fp8(x, axis):
    """x rounded through float8 e4m3 with a scale per slice along
    ``axis`` (None: one scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer(x, layers, i, *, c, quant):
    """Layer ``i`` of the stacked ``layers`` over one sequence x: (S, D)
    float32."""
    c = dict(c)
    S = x.shape[0]
    H, Kv, Dh = c["H"], c["Kv"], c["Dh"]
    lw = {k: v[i].astype(jnp.float32) for k, v in layers.items()}
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], c["eps"])
    q = _mm(h, lw["wq"], quant).reshape(S, H, Dh)
    k = _mm(h, lw["wk"], quant).reshape(S, Kv, Dh)
    v = _mm(h, lw["wv"], quant).reshape(S, Kv, Dh)
    q = _rope(_rms(q, lw["q_norm"], c["eps"]), pos, c["theta"])
    k = _rope(_rms(k, lw["k_norm"], c["eps"]), pos, c["theta"])
    G = H // Kv
    kg = jnp.repeat(k, G, axis=1)                  # (S, H, Dh)
    vg = jnp.repeat(v, G, axis=1)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kg,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(Dh)
        qpos = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vg,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, H * Dh)
    x = x + _mm(o, lw["wo"], quant)
    h = _rms(x, lw["ln2"], c["eps"])
    g = _mm(h, lw["w_gate"], quant)
    u = _mm(h, lw["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, lw["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _head(x, idx, ln_f, lm_head, *, c, quant):
    c = dict(c)
    h = _rms(x[idx], ln_f.astype(jnp.float32), c["eps"])
    return _mm(h, lm_head.astype(jnp.float32), quant)


def _consts(config: dict):
    c = config["config"]
    return tuple(sorted({
        "H": c["num_attention_heads"], "Kv": c["num_key_value_heads"],
        "Dh": c["head_dim"], "eps": float(c["rms_norm_eps"]),
        "theta": float(c["rope_theta"])}.items()))


def logits_at(config: dict, params: dict, seqs, positions, quant=None):
    """Reference logits of each sequence at the given positions.

    seqs: list of int token arrays; positions: list of int arrays (the
    positions whose next-token logits are wanted).  Returns a list of
    float32 numpy arrays (len(positions[i]), vocab).  Sequences are padded
    to a multiple of Q_BLOCK; causality keeps the padding out of every
    position that is read."""
    c = _consts(config)
    L = params["layers"]["wq"].shape[0]
    xs = []
    for s in seqs:
        n = -(-len(s) // Q_BLOCK) * Q_BLOCK
        tok = np.zeros(n, np.int32)
        tok[:len(s)] = s
        xs.append(params["embed"][jnp.asarray(tok)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for i in range(L):
            xs = [_layer(x, params["layers"], jnp.int32(i), c=c, quant=quant)
                  for x in xs]
        out = [np.asarray(_head(x, jnp.asarray(p, jnp.int32),
                                params["ln_f"], params["lm_head"],
                                c=c, quant=quant))
               for x, p in zip(xs, positions)]
    return out


def served_gaps(config: dict, params: dict, requests, quant=None):
    """For each (prompt, served tokens) pair, the gap by which each served
    token's reference logit lies below the reference's best at its
    position.  With ``quant`` set (the control), the token read at each
    position is the one the lower-precision model puts first, and its gap
    is read on the float32 reference."""
    seqs, pos = [], []
    for prompt, served in requests:
        seqs.append(np.concatenate([prompt, served[:-1]]).astype(np.int32))
        pos.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(served)))
    ref = logits_at(config, params, seqs, pos)
    if quant is not None:
        low = logits_at(config, params, seqs, pos, quant=quant)
        picks = [np.argmax(lo, axis=-1) for lo in low]
    else:
        picks = [np.asarray(served) for _, served in requests]
    return [r.max(-1) - r[np.arange(len(t)), t] for r, t in zip(ref, picks)]

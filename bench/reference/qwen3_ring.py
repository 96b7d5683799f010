"""Plain float32 Qwen3 training steps (arXiv:2505.09388; the published
``Qwen3ForCausalLM``), the yardstick for a training cell of the Qwen3
family.

Forward, per layer: x += Wo attn(rope(qnorm(Wq h)), rope(knorm(Wk h)),
Wv h) with h = rmsnorm(x) * g1, causal grouped-query softmax attention at
scale 1/sqrt(head_dim); then x += Wd (silu(Wg h2) * Wu h2) with h2 =
rmsnorm(x) * g2.  Logits = (rmsnorm(x) * gf) Wout, the output head its
own matrix (the departure the configuration file states).  Loss: mean
next-token cross-entropy plus z_weight * mean(logZ^2), as the program's
``training.step.loss_fn`` defines it.  Gradients by ``jax.grad``; AdamW
with global-norm clipping and a linear warm-up, written out.

It imports nothing of the program: no kernel, no ring, no sharding
annotation.  It runs on the arrays as the benchmark placed them, so the
compiler spreads it over their devices.  Attention is computed a block
of queries at a time, and each layer and each block is rematerialised in
the backward pass, so that it fits; neither changes its arithmetic.
Matrix products run at ``highest`` precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512           # query rows per attention block (bounds memory)


def _mm(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate the two halves of each head: x (B, S, H, Dh), pos (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal grouped-query attention, a block of Q_BLOCK queries at a
    time: q (B, S, H, Dh), k/v (B, S, Kv, Dh) -> (B, S, H * Dh)."""
    B, S, H, Dh = q.shape
    G = H // k.shape[2]
    kg = jnp.repeat(k, G, axis=2)
    vg = jnp.repeat(v, G, axis=2)
    pos = jnp.arange(S)
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, kg,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(Dh)
        qpos = start + jnp.arange(qb)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vg,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, S, qb))      # (nb, B, qb, H, Dh)
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H * Dh)


def _layer(c, x, lw):
    B, S, _ = x.shape
    H, Kv, Dh, eps = c["H"], c["Kv"], c["Dh"], c["eps"]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], eps)
    q = _mm(h, lw["wq"]).reshape(B, S, H, Dh)
    k = _mm(h, lw["wk"]).reshape(B, S, Kv, Dh)
    v = _mm(h, lw["wv"]).reshape(B, S, Kv, Dh)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, c["theta"])
    k = _rope(_rms(k, lw["k_norm"], eps), pos, c["theta"])
    x = x + _mm(_attention(q, k, v), lw["wo"])
    h = _rms(x, lw["ln2"], eps)
    g = _mm(h, lw["w_gate"])
    u = _mm(h, lw["w_up"])
    return x + _mm(jax.nn.silu(g) * u, lw["w_down"])


def loss(c, params, tokens, labels, z_weight):
    """Mean next-token cross-entropy plus z_weight * mean(logZ^2); the
    label's logit is read by a masked sum over the vocabulary."""
    c = dict(c)
    x = params["embed"][tokens]

    def body(x, lw):
        return jax.checkpoint(functools.partial(_layer, c))(x, lw), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = _mm(_rms(x, params["ln_f"], c["eps"]), params["lm_head"])
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    at = jnp.sum(jnp.where(vocab == labels[..., None], logits, 0.0), -1)
    return jnp.mean(logz - at) + z_weight * jnp.mean(logz * logz)


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``min_lr_frac * lr`` at ``total_steps``."""
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(1, opt["warmup_steps"])
    frac = min(1.0, max(0.0, (step - opt["warmup_steps"])
                        / max(1, opt["total_steps"] - opt["warmup_steps"])))
    f = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * frac))
    return opt["lr"] * f


@functools.partial(jax.jit, static_argnames=("c", "z_weight"))
def _value_and_grad(params, tokens, labels, *, c, z_weight):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, argnums=1)(c, params, tokens, labels,
                                                   z_weight)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 1, 2))
def _adamw(params, grads, state, step, lr, *, opt):
    opt = dict(opt)
    g = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, opt["clip_norm"]
                                               / (norm + 1e-9)), g)
    mu = jax.tree.map(lambda m, x: opt["b1"] * m + (1 - opt["b1"]) * x,
                      state["mu"], g)
    nu = jax.tree.map(lambda v, x: opt["b2"] * v + (1 - opt["b2"]) * x * x,
                      state["nu"], g)
    bc1 = 1 - opt["b1"] ** step
    bc2 = 1 - opt["b2"] ** step

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        new = p32 - lr * (m / bc1 / (jnp.sqrt(v / bc2) + opt["eps"])
                          + opt["weight_decay"] * p32)
        return new.astype(p.dtype)

    return (jax.tree.map(upd, params, mu, nu), {"mu": mu, "nu": nu},
            jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g))


@jax.jit
def _change_norms(p, p0):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))), p, p0)


def _consts(config: dict):
    c = config["config"]
    return tuple(sorted({
        "H": c["num_attention_heads"], "Kv": c["num_key_value_heads"],
        "Dh": c["head_dim"], "eps": float(c["rms_norm_eps"]),
        "theta": float(c["rope_theta"])}.items()))


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def train_readings(config: dict, params, batches, opt: dict,
                   z_weight: float, dtype=jnp.float32) -> dict:
    """Run ``len(batches)`` AdamW steps from ``params`` (consumed) and
    return the readings a training cell compares: each step's loss, each
    leaf's norm of the first (clipped) gradient, and each leaf's norm of
    the change of the parameters after the last step.  The starting
    weights are kept in float32 beside the trained ones, on their devices,
    and the change is summed there."""
    c = _consts(config)
    ok = tuple(sorted(opt.items()))
    p0 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    p = jax.tree.map(lambda x: x.astype(dtype), params)
    if dtype == jnp.float32:
        p = jax.tree.map(jnp.copy, p)
    del params
    zeros = jax.jit(lambda t: jax.tree.map(
        lambda x: x.astype(jnp.float32) * 0.0, t))
    state = {"mu": zeros(p), "nu": zeros(p)}
    losses, grad_norms = [], None
    for i, (tokens, labels) in enumerate(batches, start=1):
        value, grads = _value_and_grad(p, tokens, labels, c=c,
                                       z_weight=z_weight)
        p, state, gn = _adamw(p, grads, state, jnp.float32(i),
                              jnp.float32(lr_at(opt, i)), opt=ok)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = _leaf_norms(gn)
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _leaf_norms(_change_norms(p, p0))}

"""Plain float32 Mamba-2 language model and AdamW (arXiv:2405.21060; the
published ``MambaLMHeadModel`` with ``Mamba2`` layers), the yardstick for
training steps.

Per layer: x += Wout rmsnorm(y * silu(z)) * g2, where [z, xBC, dt] =
(rmsnorm(x) * g1) Win, xBC goes through a causal depthwise conv of
``d_conv`` taps with bias and silu and splits into x, B, C (one group),
dt = softplus(dt + dt_bias), A = -exp(A_log), and y is the selective
state-space recurrence run one position at a time:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t

per head, with an (headdim x d_state) state.  Logits = (rmsnorm(x) * gf)
Whead.  The loss is the mean next-token cross-entropy plus ``z_weight``
times the mean squared log-partition.  AdamW: global-norm clipping, bias
corrected moments, decoupled weight decay scaled by the learning rate,
linear warm-up then cosine decay.  Departure, shared with the program and
stated in the configuration file: the head is its own matrix.

It imports nothing of the program.  Weights come in the program's layout
(a dict of stacked per-layer arrays) only because that is how the
benchmark made them.  Matrix products run at ``highest`` precision; each
layer, and each block of ``SCAN_BLOCK`` positions of the recurrence, is
recomputed in the backward pass so that the gradient fits.

``dtype=bfloat16`` is the control: parameters held in bfloat16 and every
product, activation and state computed in it, with the optimizer's
moments in float32 and its update rounded back to bfloat16, as a
bfloat16 training path without a float32 copy would.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SCAN_BLOCK = 64


def _consts(config: dict) -> tuple:
    c, m = config["config"], config["mamba2_layer"]
    D = c["d_model"]
    return tuple(sorted({"Din": m["expand"] * D, "N": m["d_state"],
                         "P": m["headdim"], "K": m["d_conv"],
                         "eps": float(m["norm_eps"])}.items()))


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _mm(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(a.dtype)


def _recurrence(x, dt, A, Bm, Cm):
    """y_t = S_t C_t, S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, one
    position at a time.  x: (B, S, H, P); dt: (B, S, H); Bm, Cm: (B, S, N).
    Returns y: (B, S, H, P)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    blk = math.gcd(S, SCAN_BLOCK)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A).astype(state.dtype)            # (B, H)
        u = (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        state = decay[..., None, None] * state + u.astype(state.dtype)
        y = jnp.einsum("bhpn,bn->bhp", state, ct,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    def tm(a):                       # time-major, in blocks
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((S // blk, blk) + a.shape[1:])

    state0 = jnp.zeros((Bsz, H, P, N), x.dtype)
    _, ys = jax.lax.scan(block, state0, (tm(x), tm(dt), tm(Bm), tm(Cm)))
    return jnp.moveaxis(ys.reshape((S,) + ys.shape[2:]), 0, 1)


def _layer(c, x, lw):
    """One residual block over x: (B, S, D)."""
    Din, N, P, K, eps = c["Din"], c["N"], c["P"], c["K"], c["eps"]
    Bsz, S, _ = x.shape
    H = Din // P
    zxbcdt = _mm(_rms(x, lw["ln"], eps), lw["in_proj"])
    z = zxbcdt[..., :Din]
    xbc = zxbcdt[..., Din:2 * Din + 2 * N]
    dt = zxbcdt[..., 2 * Din + 2 * N:]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + S] * lw["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    xs = xbc[..., :Din].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., Din:Din + N], xbc[..., Din + N:]
    A = -jnp.exp(lw["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lw["dt_bias"].astype(jnp.float32))
    y = _recurrence(xs, dt.astype(x.dtype), A, Bm, Cm)
    y = y + lw["D_skip"].astype(x.dtype)[None, None, :, None] * xs
    y = y.reshape(Bsz, S, Din) * jax.nn.silu(z)
    return x + _mm(_rms(y, lw["gnorm"], eps), lw["out_proj"])


def loss(c, params, tokens, labels, z_weight):
    """Mean next-token cross-entropy plus z_weight * mean(logZ^2)."""
    c = dict(c)
    x = params["embed"][tokens]

    def body(x, lw):
        return jax.checkpoint(functools.partial(_layer, c))(x, lw), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = _mm(_rms(x, params["ln_f"], c["eps"]),
                 params["lm_head"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    at = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
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


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2))
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


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def train_readings(config: dict, params, batches, opt: dict,
                   z_weight: float, dtype=jnp.float32) -> dict:
    """Run ``len(batches)`` AdamW steps from ``params`` (consumed) and
    return the readings a training cell compares: each step's loss, each
    leaf's norm of the first (clipped) gradient, and each leaf's norm of
    the change of the parameters after the last step."""
    c = _consts(config)
    ok = tuple(sorted(opt.items()))
    p = jax.tree.map(lambda x: x.astype(dtype), params)
    p0 = jax.tree.map(lambda x: np.asarray(x, np.float32), p)
    state = {"mu": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
             "nu": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)}
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
    change = jax.tree.map(                  # summed in float64
        lambda a, b: float(np.linalg.norm(np.asarray(a, np.float64) - b)),
        p, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {jax.tree_util.keystr(k): v for k, v in
                             jax.tree_util.tree_flatten_with_path(change)[0]}}

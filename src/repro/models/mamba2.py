"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060).

The SSD chunked form IS a chain of dense GEMMs (intra-chunk quadratic block +
low-rank inter-chunk state passing), which is exactly the workload family the
paper's tiling methodology targets; the chunk size plays the TEU-tile role.
Sub-quadratic in sequence length -> this arch runs the long_500k shape.

Layers scan-stacked; decode keeps O(1) state (conv window + SSM state), so
a "500k-token KV cache" is a few MB of state.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .layers import gather_seq, rms_norm, shard_seq

# Pooled-serving slot layout (see serving/engine.py _write_slot): batch axis
# of every cache entry.  SSM state caches are position-free, so padded
# prefill would corrupt them — no PREFILL_TRUE_LENGTHS here.
CACHE_BATCH_AXES = {"conv": 1, "ssm": 1, "length": 0}


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64
    chunk: int = 128
    remat: bool = True
    # sequence parallelism hurts here: d_model=1024 gives tiny per-device
    # shards and GSPMD re-gathers around the SSD chunk scans (2.5x flops,
    # 5x traffic measured) — see EXPERIMENTS.md SPerf, lesson L3.
    sp: bool = False
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.d_state

    def param_count(self) -> int:
        D, Din, N, L = self.d_model, self.d_inner, self.d_state, self.n_layers
        in_proj = D * (2 * Din + 2 * N + self.n_heads)
        conv = self.d_xbc * self.d_conv
        out = Din * D
        per_layer = in_proj + conv + out + 2 * self.n_heads + Din + 2 * D
        return L * per_layer + 2 * self.vocab * D + D


def init_params(cfg: Mamba2Config, key: jax.Array) -> dict:
    D, Din, N, H, L = (cfg.d_model, cfg.d_inner, cfg.d_state, cfg.n_heads,
                       cfg.n_layers)
    ks = jax.random.split(key, 8)
    dt = cfg.dtype

    def nrm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    layers = {
        "ln": jnp.ones((L, D), dt),
        "in_proj": nrm(ks[0], (L, D, 2 * Din + 2 * N + H)),
        "conv_w": nrm(ks[1], (L, cfg.d_conv, cfg.d_xbc), 0.2),
        "conv_b": jnp.zeros((L, cfg.d_xbc), dt),
        "A_log": jnp.tile(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                          (L, 1)),
        "dt_bias": jnp.zeros((L, H), jnp.float32),
        "D_skip": jnp.ones((L, H), jnp.float32),
        "gnorm": jnp.ones((L, Din), dt),
        "out_proj": nrm(ks[2], (L, Din, D)),
    }
    return {
        "embed": nrm(ks[3], (cfg.vocab, D)),
        "layers": layers,
        "ln_f": jnp.ones((D,), dt),
        "lm_head": nrm(ks[4], (D, cfg.vocab)),
    }


def _ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """Chunked SSD scan.

    x: (B, L, H, P); dt: (B, L, H) (post-softplus); A: (H,) negative;
    Bm, Cm: (B, L, N). Returns y: (B, L, H, P).
    """
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    if L % chunk:
        # pad with dt=0 steps: decay exp(0)=1 and zero state contribution,
        # so padding is exact; the padded rows are sliced off below.
        pad = chunk - L % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Lp = x.shape[1]
    T = Lp // chunk

    def resh(a, trailing):
        return a.reshape((B, T, chunk) + trailing).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(trailing))))


    xc = resh(x.astype(jnp.float32), (H, P))       # (T, B, Q, H, P)
    dtc = resh(dt, (H,))                            # (T, B, Q, H)
    Bc = resh(Bm.astype(jnp.float32), (N,))         # (T, B, Q, N)
    Cc = resh(Cm.astype(jnp.float32), (N,))         # (T, B, Q, N)

    a = dtc * A                                     # (T, B, Q, H) log-decay
    a_cum = jnp.cumsum(a, axis=2)                   # within-chunk cumsum
    a_tot = a_cum[:, :, -1]                         # (T, B, H)

    def step(S, inp):
        xq, dtq, Bq, Cq, acum, atot = inp
        # decay from step j to end of chunk / to step i
        # intra-chunk (the "diag block" GEMM of SSD):
        idx = jnp.arange(acum.shape[1])
        causal = (idx[:, None] >= idx[None, :])[None, :, :, None]
        # mask the EXPONENT, not just the product: non-causal entries have
        # positive log-decay sums that overflow exp to inf, and
        # where(causal, inf, 0) back-propagates inf * 0 = NaN into acum.
        diff = acum[:, :, None, :] - acum[:, None, :, :]            # (B,Q,Q,H)
        Lmat = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        scores = jnp.einsum("bin,bjn->bij", Cq, Bq)                # (B,Q,Q)
        w = scores[..., None] * Lmat * dtq[:, None, :, :]           # (B,Q,Q,H)
        y_diag = jnp.einsum("bijh,bjhp->bihp", w, xq)
        # contribution of the carried state (the "low-rank" block):
        y_off = jnp.einsum("bin,bhpn->bihp", Cq, S) * \
            jnp.exp(acum)[..., None]
        # new chunk-final state
        decay_to_end = jnp.exp(atot[:, None, :] - acum)             # (B,Q,H)
        Sc = jnp.einsum("bjn,bjh,bjhp->bhpn", Bq, decay_to_end * dtq, xq)
        S = jnp.exp(atot)[..., None, None] * S + Sc
        return S, y_diag + y_off

    S0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, S0, (xc, dtc, Bc, Cc, a_cum, a_tot))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, Lp, H, P)
    return y[:, :L]


def _split_proj(cfg: Mamba2Config, zxbcdt):
    Din, N, H = cfg.d_inner, cfg.d_state, cfg.n_heads
    z = zxbcdt[..., :Din]
    xbc = zxbcdt[..., Din:Din + cfg.d_xbc]
    dt = zxbcdt[..., Din + cfg.d_xbc:]
    return z, xbc, dt


def _mix_block(cfg: Mamba2Config, lp, x, conv_state=None, ssm_state=None,
               single_step: bool = False):
    """One mamba2 mixer. x: (B, L, D) (or (B, 1, D) when single_step).
    Its parts run under the named scopes ``in_proj``, ``conv``, ``ssd``
    (the chunked scan), ``gate_norm`` and ``out_proj``, which a profiler
    trace reports with each device op."""
    B, L, D = x.shape
    Din, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.headdim
    with jax.named_scope("in_proj"):
        zxbcdt = x @ lp["in_proj"]
        z, xbc, dt = _split_proj(cfg, zxbcdt)

    with jax.named_scope("conv"):
        if single_step:
            # roll conv window: conv_state (B, d_conv-1, d_xbc)
            win = jnp.concatenate([conv_state, xbc.astype(jnp.float32)],
                                  axis=1)
            new_conv = win[:, 1:]
            conv_w = lp["conv_w"].astype(jnp.float32)  # (d_conv, d_xbc)
            xbc = jax.nn.silu((win * conv_w[None]).sum(1) +
                              lp["conv_b"].astype(jnp.float32))[:, None]
        else:
            pad = jnp.zeros((B, cfg.d_conv - 1, cfg.d_xbc), jnp.float32)
            seq = jnp.concatenate([pad, xbc.astype(jnp.float32)], axis=1)
            conv_w = lp["conv_w"].astype(jnp.float32)
            xbc = sum(seq[:, i:i + L] * conv_w[i][None, None]
                      for i in range(cfg.d_conv))
            xbc = jax.nn.silu(xbc + lp["conv_b"].astype(jnp.float32))
            new_conv = seq[:, L:]  # unused in train

    xs = xbc[..., :Din].reshape(B, -1, H, P)
    Bm = xbc[..., Din:Din + N]
    Cm = xbc[..., Din + N:]
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))       # (H,)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         lp["dt_bias"].astype(jnp.float32))

    if single_step:
        dA = jnp.exp(dt[:, 0] * A)                      # (B, H)
        Sc = jnp.einsum("bn,bh,bhp->bhpn", Bm[:, 0], dt[:, 0], xs[:, 0])
        ssm_state = dA[..., None, None] * ssm_state + Sc
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0], ssm_state)[:, None]
    else:
        with jax.named_scope("ssd"):
            y = _ssd_chunked(xs, dt, A, Bm, Cm, min(cfg.chunk, L))
        if ssm_state is None:
            ssm_state = jnp.zeros((B, H, P, N), jnp.float32)

    with jax.named_scope("gate_norm"):
        y = y + lp["D_skip"].astype(jnp.float32)[None, None, :, None] * xs
        y = y.reshape(B, -1, Din)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y.astype(cfg.dtype), lp["gnorm"], cfg.norm_eps)
    with jax.named_scope("out_proj"):
        out = y @ lp["out_proj"]
    return out, new_conv, ssm_state


def forward(cfg: Mamba2Config, params: dict, tokens: jax.Array,
            vision_embeds=None):
    x = params["embed"][tokens]

    def body(x, lp):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        if cfg.sp:
            h = gather_seq(h)
        o, _, _ = _mix_block(cfg, lp, h)
        x = x + o
        return (shard_seq(x) if cfg.sp else x), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = x @ params["lm_head"]
    return logits, 0.0


def init_cache(cfg: Mamba2Config, batch: int, max_len: int = 0,
               kv_dtype: Any = None) -> dict:
    L, H, P, N = cfg.n_layers, cfg.n_heads, cfg.headdim, cfg.d_state
    return {
        "conv": jnp.zeros((L, batch, cfg.d_conv - 1, cfg.d_xbc), jnp.float32),
        "ssm": jnp.zeros((L, batch, H, P, N), jnp.float32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg: Mamba2Config, params: dict, tokens: jax.Array, cache: dict,
            vision_embeds=None):
    """Prefill = forward pass that also leaves final (conv, ssm) states."""
    x = params["embed"][tokens]
    B, L, _ = x.shape

    def body(x, lp):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        zxbcdt = h @ lp["in_proj"]
        z, xbc, dt = _split_proj(cfg, zxbcdt)
        pad = jnp.zeros((B, cfg.d_conv - 1, cfg.d_xbc), jnp.float32)
        seq = jnp.concatenate([pad, xbc.astype(jnp.float32)], axis=1)
        conv_w = lp["conv_w"].astype(jnp.float32)
        xc = sum(seq[:, i:i + L] * conv_w[i][None, None]
                 for i in range(cfg.d_conv))
        xc = jax.nn.silu(xc + lp["conv_b"].astype(jnp.float32))
        conv_state = seq[:, L:]
        Din, N, H, P = cfg.d_inner, cfg.d_state, cfg.n_heads, cfg.headdim
        xs = xc[..., :Din].reshape(B, L, H, P)
        Bm = xc[..., Din:Din + N]
        Cm = xc[..., Din + N:]
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
        dtv = jax.nn.softplus(dt.astype(jnp.float32) +
                              lp["dt_bias"].astype(jnp.float32))
        y = _ssd_chunked(xs, dtv, A, Bm, Cm, min(cfg.chunk, L))
        # final state: replay decay over the whole sequence cheaply via the
        # same chunk recursion (recompute last chunk's S) — here we fold the
        # full sequence: S = sum_j exp(sum_{k>j} a_k) dt_j B_j x_j
        a = dtv * A
        a_rev = jnp.cumsum(a[:, ::-1], axis=1)[:, ::-1] - a
        S = jnp.einsum("bjn,bjh,bjhp->bhpn", Bm,
                       jnp.exp(a_rev) * dtv, xs)
        y = y + lp["D_skip"].astype(jnp.float32)[None, None, :, None] * xs
        y = y.reshape(B, L, Din) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y.astype(cfg.dtype), lp["gnorm"], cfg.norm_eps)
        return x + y @ lp["out_proj"], (conv_state, S)

    x, (convs, ssms) = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, -1:] @ params["lm_head"]
    cache = {"conv": convs, "ssm": ssms,
             "length": jnp.full((B,), L, jnp.int32)}
    return logits, cache


def decode_step(cfg: Mamba2Config, params: dict, tokens: jax.Array,
                cache: dict):
    x = params["embed"][tokens]
    B = x.shape[0]

    def body(x, inp):
        lp, conv_s, ssm_s = inp
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        o, conv_s, ssm_s = _mix_block(cfg, lp, h, conv_s, ssm_s,
                                      single_step=True)
        return x + o, (conv_s, ssm_s)

    x, (convs, ssms) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    return logits, {"conv": convs, "ssm": ssms,
                    "length": cache["length"] + 1}

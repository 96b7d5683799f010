"""Public jit'd wrappers around the Pallas kernels.

Each wrapper (1) picks block shapes from the paper's tile search
(``core.pallas_bridge``), (2) pads inputs to block multiples, (3) dispatches
to the Pallas kernel — interpret mode on CPU (the container), compiled Mosaic
on TPU — and (4) slices the padding back off.  ``ref.py`` holds the oracles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.pallas_bridge import (attention_block_shapes,
                                      matmul_block_shapes, round_up)
from . import attention as _attention
from . import conv2d as _conv2d
from . import correlation as _correlation
from . import matmul as _matmul
from . import paged_attention as _paged_attention


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _record_dispatch(kernel: str, **args) -> None:
    """Telemetry hook for kernel dispatch decisions (impl chosen, block
    shapes, pruning ratio).  The wrappers are jitted, so this runs at
    TRACE time — once per compiled shape, zero steady-state overhead.
    Counters land in the global registry unconditionally (rare events);
    the trace instant fires only when telemetry is enabled."""
    from repro.obs import REGISTRY, get_telemetry
    REGISTRY.counter("kernel_dispatch", kernel=kernel,
                     impl=str(args.get("impl", "pallas")))
    if "pruning_ratio" in args:
        REGISTRY.gauge("kernel_pruning_ratio", args["pruning_ratio"],
                       kernel=kernel, sq=args.get("sq"), sk=args.get("sk"))
    t = get_telemetry()
    if t.enabled:
        t.instant("kernel_dispatch", cat="kernel", kernel=kernel, **args)


def _pad_to(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul(a: jax.Array, b: jax.Array, *, block_m: int | None = None,
           block_n: int | None = None, block_k: int | None = None) -> jax.Array:
    """VectorMesh-tiled matmul: (M, K) @ (K, N) -> (M, N)."""
    M, K = a.shape
    _, N = b.shape
    if block_m is None or block_n is None or block_k is None:
        bm, bn, bk = matmul_block_shapes(max(M, 8), max(N, 128), max(K, 128))
        block_m = block_m or min(bm, 256)
        block_n = block_n or min(bn, 256)
        block_k = block_k or min(bk, 512)
    Mp, Np, Kp = (round_up(M, block_m), round_up(N, block_n),
                  round_up(K, block_k))
    _record_dispatch("matmul", M=M, N=N, K=K, block_m=block_m,
                     block_n=block_n, block_k=block_k)
    out = _matmul.matmul_pallas(
        _pad_to(a, (Mp, Kp)), _pad_to(b, (Kp, Np)),
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=_interpret())
    return out[:M, :N]


@functools.partial(jax.jit,
                   static_argnames=("stride", "dilation", "block_oh",
                                    "block_co"))
def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1, dilation: int = 1,
           block_oh: int = 8, block_co: int = 128) -> jax.Array:
    """NHWC conv, VALID padding (pad x yourself for SAME)."""
    N, IH, IW, CI = x.shape
    KH, KW, _, CO = w.shape
    OH = (IH - (KH - 1) * dilation - 1) // stride + 1
    OW = (IW - (KW - 1) * dilation - 1) // stride + 1
    block_oh = min(block_oh, OH)
    block_co = min(block_co, CO)
    OHp = round_up(OH, block_oh)
    COp = round_up(CO, block_co)
    # pad input rows so the last halo block stays in bounds
    IHp = (OHp - 1) * stride + (KH - 1) * dilation + 1
    xp = _pad_to(x, (N, max(IH, IHp), IW, CI))
    wp = _pad_to(w, (KH, KW, CI, COp))
    _record_dispatch("conv2d", oh=OH, ow=OW, ci=CI, co=CO,
                     block_oh=block_oh, block_co=block_co)
    out = _conv2d.conv2d_pallas(xp, wp, stride=stride, dilation=dilation,
                                block_oh=block_oh, block_co=block_co,
                                interpret=_interpret())
    return out[:, :OH, :OW, :CO]


@functools.partial(jax.jit, static_argnames=("radius", "block_y"))
def correlation(i1: jax.Array, i2: jax.Array, *, radius: int,
                block_y: int = 8) -> jax.Array:
    """FlowNet correlation (Eq. 3): (H, W, C) x2 -> (H, W, D, D)."""
    H, W, C = i1.shape
    block_y = min(block_y, H)
    Hp = round_up(H, block_y)
    i1p = _pad_to(i1, (Hp, W, C))
    i2p = jnp.pad(i2, ((radius, radius + (Hp - H)), (radius, radius), (0, 0)))
    out = _correlation.correlation_pallas(
        i1p, i2p, radius=radius, block_y=block_y, interpret=_interpret())
    return out[:H]


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k",
                                    "trainable", "prune"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    trainable: bool = True,
                    prune: bool = True) -> jax.Array:
    """q: (B, H, S, D), k/v: (B, Hkv, S, D) -> (B, H, S, D).

    The default path is the TRAINABLE fused kernel: forward saves only
    (o, lse) and the backward runs the Pallas dq / dkv re-stream kernels
    under a custom VJP (``trainable=False`` keeps the fwd-only kernel for
    oracle sweeps).  Block shapes come from the paper's tile search
    (``pallas_bridge.attention_block_shapes``, memoized per shape) unless
    pinned; fully-masked k-blocks are pruned from the grid schedule
    (``prune=False`` keeps the dense grid — the benchmark baseline)."""
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Sk, _ = k.shape
    if block_q is None or block_k is None:
        bq, bk = attention_block_shapes(Sq, Sk, Dh)
        block_q = block_q or bq
        block_k = block_k or bk
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    Sqp, Skp = round_up(Sq, block_q), round_up(Sk, block_k)
    qf = _pad_to(q, (B, Hq, Sqp, Dh)).reshape(B * Hq, Sqp, Dh)
    kf = _pad_to(k, (B, Hkv, Skp, Dh)).reshape(B * Hkv, Skp, Dh)
    vf = _pad_to(v, (B, Hkv, Skp, Dh)).reshape(B * Hkv, Skp, Dh)
    real, total = _attention.scheduled_block_counts(
        Sqp, Skp, block_q=block_q, block_k=block_k, causal=causal,
        window=window)
    if not prune:
        real = total                      # dense grid: nothing skipped
    _record_dispatch("flash_attention",
                     impl="train" if trainable else "fwd",
                     sq=Sq, sk=Sk, block_q=block_q, block_k=block_k,
                     scheduled_blocks=real, dense_blocks=total,
                     pruning_ratio=real / total if total else 1.0)
    if trainable:
        spec = _attention.FlashSpec(
            causal=causal, window=window, block_q=block_q, block_k=block_k,
            scale=1.0 / math.sqrt(Dh), kv_len=Sk, q_len=Sq, prune=prune,
            interpret=_interpret())
        out = _attention.flash_attention_train(spec, qf, kf, vf)
    else:
        out, _ = _attention.flash_attention_fwd_pallas(
            qf, kf, vf, causal=causal, window=window, block_q=block_q,
            block_k=block_k, kv_len=Sk, q_len=Sq, prune=prune,
            interpret=_interpret())
    return out.reshape(B, Hq, Sqp, Dh)[:, :, :Sq]


@functools.partial(jax.jit, static_argnames=("block_k",))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, block_k: int = 512) -> jax.Array:
    """q: (B, H, D) one token; caches: (B, Hkv, S, D); lengths: (B,).

    Returns (B, H, D)."""
    B, Hq, Dh = q.shape
    _, Hkv, S, _ = k_cache.shape
    G = Hq // Hkv
    block_k = min(block_k, S)
    Sp = round_up(S, block_k)
    qf = q.reshape(B, Hkv, G, Dh).reshape(B * Hkv, G, Dh)
    kf = _pad_to(k_cache, (B, Hkv, Sp, Dh)).reshape(B * Hkv, Sp, Dh)
    vf = _pad_to(v_cache, (B, Hkv, Sp, Dh)).reshape(B * Hkv, Sp, Dh)
    lens = jnp.repeat(lengths, Hkv).astype(jnp.int32)
    _record_dispatch("flash_decode", batch=B, s=S, block_k=block_k)
    out = _attention.flash_decode_pallas(
        qf, kf, vf, lens, block_k=block_k, interpret=_interpret())
    return out.reshape(B, Hkv, G, Dh).reshape(B, Hq, Dh)


@jax.jit
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, lengths: jax.Array,
                       k_scale: jax.Array | None = None,
                       v_scale: jax.Array | None = None,
                       layer: jax.Array | None = None) -> jax.Array:
    """Paged decode: q (B, H, D) one token; pools (L, P, page, Hkv, D) of
    all layers with ``layer`` the one attended, or one layer's (P, page,
    Hkv, D) with ``layer`` None; page_table (B, max_pages) physical page
    ids; lengths (B,) valid tokens; optional int8-pool scales (L, P, page,
    Hkv) or (P, page, Hkv).  Returns (B, H, D).

    The kernel reads the pools, table and lengths as they are stored; the
    pages each grid step copies come from the page's bytes."""
    if layer is None:                  # one layer's pool: L = 1, l = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    B, H, Dh = q.shape
    _, P, page_size, Hkv, _ = k_pages.shape
    MP = int(page_table.shape[1])
    ppb = _paged_attention.pages_per_block(
        page_size, Hkv, Dh, k_pages.dtype.itemsize, MP)
    _record_dispatch("paged_flash_decode",
                     impl="int8" if k_scale is not None else "pallas",
                     batch=B, pages=P, page_size=page_size,
                     max_pages=MP, pages_per_block=ppb)
    return _paged_attention.paged_flash_decode_pallas(
        q, k_pages, v_pages, page_table.astype(jnp.int32),
        lengths.astype(jnp.int32), layer, k_scale, v_scale,
        pages_per_block=ppb, interpret=_interpret())

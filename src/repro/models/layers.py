"""Shared neural layers (pure functions over param dicts).

Conventions:
  * params are plain dicts of jnp arrays; layer stacks carry a leading layer
    axis and are traversed with jax.lax.scan (compact HLO at any depth).
  * activations default to bf16; params bf16; accumulations f32.
  * attention is grouped (GQA) and supports qk-norm, qkv-bias, causal and
    sliding-window masks. The XLA path is used on the CPU; the Pallas
    flash kernels (repro.kernels) are the TPU path,
    selected via ``impl='pallas'``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime import compat


def shard_seq(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism at layer boundaries.

    Shards the sequence dim of (B, S, D) activations over the `model` axis
    so the per-layer residuals saved for backward shrink by the TP degree
    (the TP all-gather that follows is traffic the block pays anyway).
    No-op outside a mesh context or when S does not divide."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return x
    if "model" not in mesh.axis_names:
        return x
    m = mesh.shape["model"]
    if m == 1 or x.ndim != 3 or x.shape[1] % m != 0:
        return x
    UC = jax.sharding.PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(UC, "model", UC))


def gather_seq(x: jax.Array) -> jax.Array:
    """Inverse of shard_seq: all-gather the sequence dim at block entry so
    the mixer (attention/SSD/LRU) computes on the full sequence — GSPMD
    emits exactly one all-gather here and one reduce-scatter at the residual
    add (the Megatron-SP schedule), instead of resharding inside the
    attention scans."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return x
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return x
    if x.ndim != 3:
        return x
    UC = jax.sharding.PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(UC, None, UC))


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e6) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) — XLA paths for lowering; Pallas kernels are the TPU path.
# ---------------------------------------------------------------------------

def _flash_mode(S: int, Sk: int, override: str | None = None) -> str:
    """Resolve the attention engine ('pallas' trainable kernel | 'xla')
    for one call.  The policy lives in ``configs.base`` (explicit
    override > REPRO_FLASH_ATTN env > default); imported lazily to keep
    the configs<->models import order acyclic."""
    from repro.configs import base as cbase
    pol = cbase.flash_attn_policy(override)
    return cbase.decide_flash(pol, seq_len=S, kv_len=Sk,
                              on_tpu=jax.default_backend() == "tpu")


def _flash_pallas(q, k, v, *, causal, window):
    """Dispatch to the trainable fused Pallas kernel (custom-VJP fwd+bwd,
    pruned grid).  Under a data-parallel mesh the call shard_maps over the
    batch axes (attention has no cross-batch terms, so batch sharding is
    exact); a mesh with a live ``model`` axis returns None — the
    ring/replicated context-parallel paths own those shapes."""
    from repro.kernels import ops as kops

    def call(q, k, v):
        o = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window)
        return o.transpose(0, 2, 1, 3)

    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return call(q, k, v)
    try:
        if mesh._are_all_axes_manual:    # already inside a shard_map
            return call(q, k, v)
    except AttributeError:
        pass
    if "model" in mesh.axis_names and mesh.shape["model"] > 1:
        return None
    from jax.sharding import PartitionSpec as P

    from repro.parallel.ring_attention import data_axes_spec
    dspec = data_axes_spec(mesh, q.shape[0])
    if dspec is None:
        return None
    sp = P(dspec, None, None, None)
    fn = compat.shard_map(call, mesh=mesh, in_specs=(sp, sp, sp),
                          out_specs=sp)
    return fn(q, k, v)


def _ring_mode(S: int, m: int, override: str | None = None) -> str:
    """Resolve the context-parallel mode ('ring' | 'replicated' | 'off')
    for a global sequence of S on an m-wide model axis.  The policy lives
    in ``configs.base`` (explicit override > REPRO_RING_ATTN env >
    default); imported lazily to keep the configs<->models import order
    acyclic."""
    from repro.configs import base as cbase
    return cbase.decide_ring(cbase.ring_attn_policy(override),
                             seq_len=S, ring_size=m)


def _grouped_scores_full(q, k, v, *, causal, window, q_offset=0):
    """Full-mask attention. q: (B, S, H, Dh); k/v: (B, Sk, Hkv, Dh)."""
    B, S, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(Dh)
    qpos = q_offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((S, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, Dh).astype(q.dtype)


def _grouped_scores_chunked(q, k, v, *, causal, window, chunk: int = 1024,
                            q_offset=0):
    """Online-softmax scan over kv chunks for ONE q block (flash inner loop).

    q: (B, Sq, H, Dh) with global position offset q_offset (may be traced).
    The (Sq, Sk) score matrix is never materialized.
    """
    B, S, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    assert Sk % chunk == 0, (Sk, chunk)
    # keep q/k/v in model dtype (bf16): the MXU dots accumulate in f32 via
    # preferred_element_type, and the p tensor is stored bf16 like the flash
    # kernel — this halves the attention HBM traffic vs f32 intermediates
    # (§Perf iteration C2/A1).
    qg = q.reshape(B, S, Hkv, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    n_chunks = Sk // chunk
    kc = k.reshape(B, n_chunks, chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(S)[:, None]

    def step(carry, inp):
        m, l, acc = carry
        ci, kb, vb = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = jnp.ones((S, chunk), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    # inside shard_map the carries must carry the same varying-manual
    # axes as the data they will be combined with
    m0 = compat.match_vma(jnp.full((B, Hkv, G, S), -1e30, jnp.float32), qg)
    l0 = compat.match_vma(jnp.zeros((B, Hkv, G, S), jnp.float32), qg)
    a0 = compat.match_vma(jnp.zeros((B, Hkv, G, S, Dh), jnp.float32), qg)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    o = acc / jnp.where(l == 0, 1.0, l)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh).astype(q.dtype)


def _attention_blocked(q, k, v, *, causal, window, q_chunk=2048,
                       k_chunk=4096, base_offset=0, use_constraints=True):
    """Flash-style double blocking in pure XLA: outer scan over q blocks,
    inner online-softmax scan over kv blocks. Peak temp is one
    (q_chunk x k_chunk) tile per (batch, head) instead of the full S x Sk
    score matrix — this is what makes 32k-seq cells lowerable (and is the
    same schedule as kernels/attention.py, whose Pallas version is the
    real-TPU path).

    base_offset: global position of q row 0 (traced OK) — used by the
    ring/shard_map path where each device holds a sequence slice."""
    B, S, H, Dh = q.shape
    q_chunk = min(q_chunk, S)
    while S % q_chunk:          # largest block size that divides S
        q_chunk -= 1
    Sk = k.shape[1]
    k_chunk = min(k_chunk, Sk)
    while Sk % k_chunk:
        k_chunk -= 1
    nq = S // q_chunk
    qb = q.reshape(B, nq, q_chunk, H, Dh).transpose(1, 0, 2, 3, 4)
    if use_constraints:
        qb = _shard_qblocks(qb)

    def qstep(_, inp):
        qi, qblk = inp
        o = _grouped_scores_chunked(
            qblk, k, v, causal=causal, window=window,
            chunk=k_chunk, q_offset=base_offset + qi * q_chunk)
        return None, o

    # without this, scan-of-scans backward saves every inner-chunk residual
    # — i.e. the full S x Sk score matrix in f32, just distributed. With it,
    # backward recomputes one q-block at a time (flash-style).
    qstep = jax.checkpoint(
        qstep, policy=jax.checkpoint_policies.nothing_saveable)
    _, os = jax.lax.scan(qstep, None, (jnp.arange(nq), qb))
    return os.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)


def _attention_ring(q, k, v, *, causal, window, ring: str | None = None):
    """Context-parallel attention over the `model` mesh axis.

    Two schedules behind one policy (``configs.base.ring_attn_policy``;
    ``ring`` overrides the mode for this call):

    * ``ring`` — the ppermute ring (§Perf B6, the paper's FIFO mesh):
      k/v stay SEQUENCE-SHARDED and hop neighbour-to-neighbour while each
      device folds the visiting shard into its rows' online softmax.  The
      memory-flat custom VJP in ``parallel.ring_attention`` (backward
      recomputes each hop's score tile; dk/dv accumulators circulate with
      the shards) is what lets this be the DEFAULT long-sequence path.
    * ``replicated`` — the §Perf B5 shard_map: q sequence-sharded against
      replicated k/v; shard_map AD transposes the replicated k/v into ONE
      psum of dk/dv per layer.  The XLA fallback below the ring's
      sequence threshold.

    Returns None when inapplicable (no mesh / indivisible shapes / mode
    'off') so the caller can fall back to the constraint-based layout."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return None
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return None
    try:
        if mesh._are_all_axes_manual:    # already inside a shard_map
            return None
    except AttributeError:
        pass
    m = mesh.shape["model"]
    B, S, H, Dh = q.shape
    if S % m != 0 or k.shape[1] != S:
        return None
    from repro.parallel.ring_attention import data_axes_spec, ring_attention

    mode = _ring_mode(S, m, ring)
    if mode == "off":
        return None
    if mode == "ring":
        out = ring_attention(q, k, v, causal=causal, window=window,
                             mesh=mesh)
        if out is not None:
            return out

    dspec = data_axes_spec(mesh, B)
    from jax.sharding import PartitionSpec as P

    def body(q_l, k_l, v_l):
        off = jax.lax.axis_index("model") * q_l.shape[1]
        return _attention_blocked(q_l, k_l, v_l, causal=causal,
                                  window=window, base_offset=off,
                                  use_constraints=False)

    fn = compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(dspec, "model", None, None),
                  P(dspec, None, None, None),
                  P(dspec, None, None, None)),
        out_specs=P(dspec, "model", None, None),
    )
    return _named_out(fn(q, k, v))


def _named_out(o):
    """An XLA attention path's output under the name a remat policy keeps
    (the flash and ring VJPs name their own output and logsumexp)."""
    from jax.ad_checkpoint import checkpoint_name

    from repro.kernels.attention import OUT_NAME
    return checkpoint_name(o, OUT_NAME)


def _shard_attn_inputs(q, k, v):
    """Context-parallel attention layout (§Perf iteration C3).

    Without a constraint, GSPMD shards the CONTRACTING Dh dim of the score
    einsums whenever the head count doesn't divide the model axis (24 or 40
    heads on a 16-wide mesh) and emits a partial-sum all-reduce of the score
    tensor per kv-chunk step — hundreds of GB/device. Instead: shard q's
    SEQUENCE over `model` and replicate k/v (k/v are kv-heads-only, a few
    hundred MB) — every device computes its own q rows, no sharded
    contractions, attention traffic drops by the TP degree."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return q, k, v
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return q, k, v
    UC = jax.sharding.PartitionSpec.UNCONSTRAINED
    P = jax.sharding.PartitionSpec
    k = jax.lax.with_sharding_constraint(k, P(UC, None, None, None))
    v = jax.lax.with_sharding_constraint(v, P(UC, None, None, None))
    return q, k, v


def _shard_qblocks(qb):
    """Shard the q-chunk rows of the blocked layout (nq, B, qc, H, Dh) over
    `model` — the constraint must live on the POST-reshape tensor or GSPMD
    re-replicates every scan step (§Perf iteration C3')."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return qb
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return qb
    if qb.shape[2] % mesh.shape["model"] != 0:
        return qb
    UC = jax.sharding.PartitionSpec.UNCONSTRAINED
    P = jax.sharding.PartitionSpec
    return jax.lax.with_sharding_constraint(
        qb, P(None, UC, "model", None, None))


def attention(q, k, v, *, causal=True, window=None, impl=None,
              full_threshold: int = 2048, q_offset: int = 0,
              ring: str | None = None):
    """Dispatch: the trainable fused Pallas kernel when the flash policy
    picks it (TPU auto / forced — the DEFAULT training path on real
    hardware), else full-mask XLA for short seqs and context-parallel
    shard_map (ppermute ring / replicated k/v, per the ring policy) or
    double-blocked flash-style scan for long ones.  ``impl`` overrides
    the flash policy ('pallas' | 'xla'; None/'auto' resolves via
    REPRO_FLASH_ATTN); ``ring`` overrides the ring-policy mode.

    Every path leaves what its backward reads under the names of
    ``kernels.attention``, once: the flash and ring VJPs their output and
    logsumexp, the XLA paths their output."""
    if impl in (None, "auto", "pallas", "xla"):
        mode = _flash_mode(q.shape[1], k.shape[1],
                           None if impl in (None, "auto") else impl)
    else:
        raise ValueError(f"attention impl {impl!r} not in "
                         "(None, 'auto', 'pallas', 'xla')")
    # the kernel wrapper masks in local positions; offset callers (chunked
    # q against a longer kv) stay on the XLA paths, which honor q_offset
    offset_free = isinstance(q_offset, int) and q_offset == 0
    if mode == "pallas" and offset_free:
        out = _flash_pallas(q, k, v, causal=causal, window=window)
        if out is not None:
            return out
    if max(q.shape[1], k.shape[1]) > full_threshold:
        out = _attention_ring(q, k, v, causal=causal, window=window,
                              ring=ring)
        if out is not None:
            return out
        q, k, v = _shard_attn_inputs(q, k, v)
        return _named_out(_attention_blocked(q, k, v, causal=causal,
                                             window=window))
    q, k, v = _shard_attn_inputs(q, k, v)
    return _named_out(_grouped_scores_full(q, k, v, causal=causal,
                                           window=window, q_offset=q_offset))


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 KV quantization.

    x: (..., Dh) -> (int8 same shape, f32 scale (...,)). Halves decode-cache
    HBM vs bf16."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def decode_attention(q, k_cache, v_cache, lengths, k_scale=None,
                     v_scale=None, chunk: int = 4096):
    """One-token attention against a cache. q: (B, 1, H, Dh);
    caches: (B, S, Hkv, Dh) (bf16, or int8 + (B, S, Hkv) scales);
    lengths: (B,).

    Long caches process in chunks with an online softmax so quantized
    caches dequantize ONE chunk at a time — a full-cache f32 dequant temp
    would cost 4x the int8 cache's HBM."""
    B, _, H, Dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Dh).astype(jnp.float32)
    scale = 1.0 / math.sqrt(Dh)

    def dense(kc, vc, pos0):
        kcf = dequantize_kv(kc, k_scale) if k_scale is not None and \
            kc.dtype == jnp.int8 else kc
        vcf = dequantize_kv(vc, v_scale) if v_scale is not None and \
            vc.dtype == jnp.int8 else vc
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kcf.astype(jnp.float32)) * scale
        mask = (pos0 + jnp.arange(kc.shape[1]))[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        return s, vcf

    if S <= chunk or S % chunk != 0:
        s, vcf = dense(k_cache, v_cache, 0)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p, vcf.astype(jnp.float32))
        return o.reshape(B, 1, H, Dh).astype(q.dtype)

    n = S // chunk

    def resh(a, trail):
        return a.reshape((B, n, chunk) + trail).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(trail))))

    kc = resh(k_cache, (Hkv, Dh))
    vc = resh(v_cache, (Hkv, Dh))
    ks = resh(k_scale, (Hkv,)) if k_scale is not None else None
    vs = resh(v_scale, (Hkv,)) if v_scale is not None else None

    def step(carry, inp):
        m, l, acc = carry
        if ks is not None:
            ci, kb, vb, ksb, vsb = inp
            kb = dequantize_kv(kb, ksb)
            vb = dequantize_kv(vb, vsb)
        else:
            ci, kb, vb = inp
        s = jnp.einsum("bkgd,bskd->bkgs", qg,
                       kb.astype(jnp.float32)) * scale
        mask = (ci * chunk + jnp.arange(chunk))[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgs,bskd->bkgd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    init = (jnp.full((B, Hkv, G), -1e30, jnp.float32),
            jnp.zeros((B, Hkv, G), jnp.float32),
            jnp.zeros((B, Hkv, G, Dh), jnp.float32))
    xs = (jnp.arange(n), kc, vc) if ks is None else \
        (jnp.arange(n), kc, vc, ks, vs)
    (m, l, acc), _ = jax.lax.scan(step, init, xs)
    o = acc / jnp.where(l == 0, 1.0, l)[..., None]
    return o.reshape(B, 1, H, Dh).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           k_scale=None, v_scale=None, *, layer=None,
                           impl="xla"):
    """Attention of freshly written tokens against a paged KV pool.

    q: (B, T, H, Dh) — token t of row b sits at position ``lengths[b] + t``
    and its K/V have already been scattered into the pool, so it attends
    every position <= its own.  k_pages/v_pages: (L, P, page, Hkv, Dh) the
    global pools of all layers, of which ``layer`` (an int32 scalar) is
    attended, or ONE layer's (P, page, Hkv, Dh) with ``layer`` None;
    page_table: (B, max_pages) physical ids (0 = trash, always masked by
    the position bound); lengths: (B,) tokens cached BEFORE this step's
    writes.  Scales (int8 pools): (L, P, page, Hkv) or (P, page, Hkv) f32.

    ``impl='pallas'`` (T == 1 only) dispatches to the paged flash-decode
    kernel, which chases the page table inside the grid — no gathered
    contiguous cache ever materializes.  The XLA path gathers the mapped
    pages of the layer with one index (bounded by the page-table slice the
    engine passes, NOT by max_len; no layer's pool is sliced out) and runs
    a masked softmax; it is the CPU/equivalence path."""
    B, T, H, Dh = q.shape
    P, page, Hkv, _ = k_pages.shape[-4:]
    if impl in (None, "auto"):
        # decode q is one token; the flash policy's min-seq threshold is a
        # prefill knob, so auto here is purely a backend question
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    from repro.kernels.ops import _record_dispatch
    _record_dispatch("paged_decode_attention",
                     impl=impl if (impl == "pallas" and T == 1) else "xla",
                     t=T, page_size=page, pages=P)
    if impl == "pallas" and T == 1:
        from repro.kernels import ops as kops
        o = kops.paged_flash_decode(q[:, 0], k_pages, v_pages, page_table,
                                    lengths + 1, k_scale, v_scale, layer)
        return o[:, None].astype(q.dtype)
    G = H // Hkv
    S = page_table.shape[1] * page
    scale = 1.0 / math.sqrt(Dh)

    idx = page_table if layer is None else (layer, page_table)

    def gather(pages, scales):
        x = pages[idx].astype(jnp.float32)         # (B, MP, page, Hkv, D)
        if scales is not None:
            x = x * scales[idx][..., None]
        return x.reshape(B, S, Hkv, Dh)

    k = gather(k_pages, k_scale)
    v = gather(v_pages, v_scale)
    qg = q.reshape(B, T, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k) * scale
    limit = lengths[:, None] + jnp.arange(T)[None, :]          # (B, T)
    mask = jnp.arange(S)[None, None, :] <= limit[:, :, None]   # (B, T, S)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v)
    return o.reshape(B, T, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def geglu(x, w_gate, w_up, w_down):
    h = jax.nn.gelu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity-bounded dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden
    capacity_factor: float = 1.25


def _moe_route(xt, router, K):
    """Shared routing math. xt: (T, D) -> gate_vals/gate_idx (T, K), probs."""
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, gate_idx, probs


def _moe_aux(probs, gate_idx, E, T, K):
    me = probs.mean(0)
    ce = jnp.bincount(gate_idx.reshape(-1), length=E).astype(jnp.float32) / \
        (T * K)
    return E * jnp.sum(me * ce)


def _moe_local(x, params, cfg: MoEConfig):
    """Single-device / data-local MoE: capacity-bounded scatter dispatch.

    Used directly on small meshes and as the per-shard body of the TP mode;
    capacities here are LOCAL token counts, so buffers stay per-device-sized.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    gate_vals, gate_idx, probs = _moe_route(xt, params["router"], K)

    C = max(1, int(cfg.capacity_factor * T * K / E))
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)        # (T, K, E)
    flat = onehot.reshape(T * K, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
    pos = (pos_in_expert * onehot).sum(-1)                       # (T, K)
    keep = pos < C
    gate_vals = gate_vals * keep

    disp = jnp.zeros((E, C, D), x.dtype)
    e_idx = gate_idx.reshape(-1)
    c_idx = jnp.where(keep.reshape(-1), pos.reshape(-1), 0)
    t_idx = jnp.repeat(jnp.arange(T), K)
    contrib = jnp.where(keep.reshape(-1)[:, None], xt[t_idx], 0)
    disp = disp.at[e_idx, c_idx].add(contrib)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, params["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", disp, params["w_up"])
    eo = jnp.einsum("ecf,efd->ecd", h, params["w_down"])         # (E, C, D)

    gathered = eo[e_idx, c_idx].astype(jnp.float32) * \
        gate_vals.reshape(-1)[:, None]
    out = jnp.zeros((T, D), jnp.float32).at[t_idx].add(gathered)
    aux = _moe_aux(probs, gate_idx, E, T, K)
    return out.reshape(B, S, D).astype(x.dtype), aux


def _moe_ep_body(x, router, wg, wu, wd, *, cfg: MoEConfig, n_groups: int,
                 model_axis: str):
    """Expert-parallel shard body: experts live on `model`-axis devices;
    tokens travel to their experts over all-to-all (and back).

    Deterministic slot layout: the send buffer to destination group g is
    e_per blocks of c slots (one block per expert owned by g), so after the
    all-to-all a reshape+transpose lines tokens up per local expert — no
    second dispatch pass.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    e_per = E // n_groups
    T = B * S
    xt = x.reshape(T, D)
    gate_vals, gate_idx, probs = _moe_route(xt, router, K)

    c = max(1, int(cfg.capacity_factor * T * K / E))   # per-expert capacity
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)        # (T, K, E)
    flat = onehot.reshape(T * K, E)
    pos = ((jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
           * onehot).sum(-1)                                     # (T, K)
    keep = pos < c
    gate_vals = gate_vals * keep

    grp = gate_idx // e_per                                      # (T, K)
    eloc = gate_idx % e_per
    slot = eloc * c + jnp.where(keep, pos, 0)                    # within group
    send = jnp.zeros((n_groups, e_per * c, D), x.dtype)
    t_idx = jnp.repeat(jnp.arange(T), K)
    contrib = jnp.where(keep.reshape(-1)[:, None], xt[t_idx], 0)
    send = send.at[grp.reshape(-1), slot.reshape(-1)].add(contrib)

    # FIFO-mesh moment: tokens hop to their expert's device and back.
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                              concat_axis=0, tiled=True)
    # (n_groups(src), e_per * c, D) -> (e_per, n_groups * c, D)
    recv = recv.reshape(n_groups, e_per, c, D).transpose(1, 0, 2, 3) \
        .reshape(e_per, n_groups * c, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg)) * \
        jnp.einsum("ecd,edf->ecf", recv, wu)
    eo = jnp.einsum("ecf,efd->ecd", h, wd)         # (e_per, n_groups*c, D)

    back = eo.reshape(e_per, n_groups, c, D).transpose(1, 0, 2, 3) \
        .reshape(n_groups, e_per * c, D)
    back = jax.lax.all_to_all(back, model_axis, split_axis=0,
                              concat_axis=0, tiled=True)

    gathered = back[grp.reshape(-1), slot.reshape(-1)].astype(jnp.float32) * \
        gate_vals.reshape(-1)[:, None]
    out = jnp.zeros((T, D), jnp.float32).at[t_idx].add(gathered)
    aux = _moe_aux(probs, gate_idx, E, T, K)
    return out.reshape(B, S, D).astype(x.dtype), aux


def moe_layer(x: jax.Array, params: dict[str, jax.Array],
              cfg: MoEConfig) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D); params: router (D, E), w_gate/w_up (E, D, F),
    w_down (E, F, D). Returns (out, aux_loss).

    Distribution dispatch:
      * no mesh (tests)                    -> local capacity dispatch
      * E divisible by the model axis     -> expert parallelism (shard_map +
        all-to-all; capacities are per-device, so buffers never scale with
        the global batch)
      * otherwise (e.g. granite's 40e/16) -> TP-in-expert: every device
        keeps all experts with 1/16 of each FFN, tokens stay put, psum after
        w_down.
    """
    mesh = compat.get_abstract_mesh()
    if (mesh is None or getattr(mesh, "empty", False)
            or "model" not in getattr(mesh, "axis_names", ())
            or mesh.shape["model"] == 1):
        return _moe_local(x, params, cfg)

    from jax.sharding import PartitionSpec as P
    msize = mesh.shape["model"]
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dspec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    E = cfg.n_experts
    lp = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}

    if E % msize == 0:
        import functools
        body = functools.partial(_moe_ep_body, cfg=cfg, n_groups=msize,
                                 model_axis="model")
        # Tokens enter SEQUENCE-sharded over `model` (the SP boundary
        # layout): each device routes its own S/msize slice — exact FLOPs.
        # Decode (S=1) can't split the sequence; tokens are then replicated
        # over `model` and the duplicate compute de-duplicated by psum/m
        # (MoE decode FLOPs are negligible).
        seq_split = x.shape[1] % msize == 0
        x_spec = P(dspec, "model", None) if seq_split else P(dspec, None,
                                                             None)

        def wrapped(x, router, wg, wu, wd):
            out, aux = body(x, router, wg, wu, wd)
            axes = daxes + ("model",)
            if not seq_split:
                out = jax.lax.psum(out, "model") / msize
                aux = compat.pcast(aux, ("model",), to="varying")
            n = 1
            for a in axes:
                n *= jax.lax.psum(1, a)
            return out, jax.lax.psum(aux, axes) / n

        fn = compat.shard_map(
            wrapped, mesh=mesh,
            in_specs=(x_spec, P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(x_spec, P()),
        )
        return fn(x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])

    # Non-divisible expert count (granite's 40e on a 16-wide axis).
    seq_split = x.shape[1] % msize == 0
    axes_all = daxes + ("model",)

    if seq_split:
        # Token-split over `model`: each device routes/computes its own
        # S/msize tokens against (temporarily gathered) full expert weights
        # — dispatch buffers shrink by msize and router compute de-dupes.
        def split_body(x, router, wg, wu, wd):
            out, aux = _moe_local(x, {"router": router, "w_gate": wg,
                                      "w_up": wu, "w_down": wd}, cfg)
            n = 1
            for a in axes_all:
                n *= jax.lax.psum(1, a)
            return out, jax.lax.psum(aux, axes_all) / n

        fn = compat.shard_map(
            split_body, mesh=mesh,
            in_specs=(P(dspec, "model", None), P(None, None),
                      P(None, None, None), P(None, None, None),
                      P(None, None, None)),
            out_specs=(P(dspec, "model", None), P()),
        )
        return fn(x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])

    # Decode fallback: tokens replicated over `model`, per-expert FFN dim
    # sharded (TP-in-expert), psum after the down-projection.
    def tp_body(x, router, wg, wu, wd):
        out, aux = _moe_local(x, {"router": router, "w_gate": wg,
                                  "w_up": wu, "w_down": wd}, cfg)
        out = jax.lax.psum(out, "model")
        aux = compat.pcast(aux, ("model",), to="varying")
        n = 1
        for a in axes_all:
            n *= jax.lax.psum(1, a)
        return out, jax.lax.psum(aux, axes_all) / n

    fn = compat.shard_map(
        tp_body, mesh=mesh,
        in_specs=(P(dspec, None, None), P(None, None),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, "model", None)),
        out_specs=(P(dspec, None, None), P()),
    )
    return fn(x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])

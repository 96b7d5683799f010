"""Flash attention Pallas kernels (prefill fwd + bwd + decode), GQA/windowed.

Attention IS the paper's spatial-matching workload at LM scale: QK^T is
Eq. (3) with the search window = the causal (or sliding) window, and the
online-softmax accumulator is the PSum buffer held stationary while the
temporal index (the kv block) streams — the same output-stationary schedule
``core.tiling`` derives for Eq. (4). GQA enters through the K/V index maps:
the q-head grid axis has zero partial derivative against the kv head beyond
its group, so K/V blocks are SHARED across the q-heads of a group exactly
like Fig. 2 shares E between P and Q.

Three schedule ideas from the paper/related work live here:

* **Pair-table grid (Eyeriss-v2-style pruning).**  Instead of a dense
  rectangular ``(nq, nk)`` grid with ``pl.when`` no-ops on fully-masked
  tiles, the (q-block, k-block) pairs that survive the causal/sliding-
  window band are enumerated ON THE HOST into a static int32 schedule
  table, passed as a scalar-prefetch operand; the BlockSpec index maps
  chase it in-grid exactly like the paged kernel chases its page table.
  Fully-masked k-blocks are never scheduled — skipped FIFO hops, not
  streamed-and-discarded ones.  Causal cuts the scheduled tiles ~2x
  (nq*(nq+1)/2 of nq*nk), a sliding window to the band width.

* **Backward = PSum drain + re-stream.**  The forward saves only
  ``(o, lse)`` (same residual contract as ``parallel/ring_attention``);
  the dq kernel re-streams k-blocks holding a q-row accumulator
  stationary, the dk/dv kernel re-streams q-blocks holding a k-column
  accumulator stationary, each recomputing its score tile from
  ``(q, k, lse)`` — two more passes of the identical output-stationary
  schedule, never materializing S x S.

* **Traced position offsets.**  Ring attention folds one visiting shard
  per hop; the shard's global offset is a traced ``axis_index``.  Offsets
  ride as a second scalar-prefetch operand so the very same kernels serve
  the single-device path (static offsets, pruned schedule) and the ring's
  per-hop fold (traced offsets, dense schedule).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pallas_bridge import pow2_floor
from repro.runtime import compat

NEG_INF = -1e30  # avoid nan from (-inf) - (-inf)
# Mosaic tiles the last two dims of every block by (8, 128); a per-row f32
# vector such as lse/delta therefore travels to and from the kernels
# broadcast across one lane-dense 128-wide tile, (BH, S, LANES), and the
# wrappers slice or broadcast it back to (BH, S).
LANES = 128

# The names under which a trainable attention's forward hands its output
# and logsumexp to its backward.  A remat policy that saves both
# (``models.transformer.forward``) leaves the backward nothing of the
# attention forward to recompute; the XLA attention paths, which have no
# VJP of their own, name their output alone (``models.layers.attention``).
OUT_NAME, LSE_NAME = "attn_out", "attn_lse"


def name_residuals(o: jax.Array, lse: jax.Array):
    """``(o, lse)`` under :data:`OUT_NAME` and :data:`LSE_NAME`."""
    return checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)


def _out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """Out-shape typed with the varying mesh axes of ``like`` — inside
    shard_map (the ring's per-hop fold) pallas_call requires the vma."""
    axes = frozenset().union(*(compat.vma(x) for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=axes)


def _lanes(x: jax.Array) -> jax.Array:
    """(..., S) f32 -> (..., S, LANES): the kernels' lse/delta layout."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


# ---------------------------------------------------------------------------
# Host-side pair-table schedules (the pruned grid)
# ---------------------------------------------------------------------------

def _row_range(iq: int, *, nk: int, block_q: int, block_k: int,
               causal: bool, window: int | None, kv_len: int,
               q_len: int) -> tuple[int, int]:
    """Inclusive [lo, hi] k-block range that q-block ``iq`` touches, or
    (0, -1) when the whole row is masked (padded q rows / empty bands)."""
    q_lo = iq * block_q
    q_hi = min(q_lo + block_q, q_len) - 1
    if q_hi < q_lo:                       # fully-padded q block
        return 0, -1
    lo, hi = 0, nk - 1
    hi = min(hi, (kv_len - 1) // block_k)    # never stream padded k blocks
    if causal:
        hi = min(hi, q_hi // block_k)
    if window is not None:
        # need some kpos with q_lo - kpos < window, i.e. k_hi > q_lo - window
        lo = max(lo, -(-(q_lo - window + 2 - block_k) // block_k))
    return lo, hi


@functools.lru_cache(maxsize=None)
def _pair_schedule(nq: int, nk: int, block_q: int, block_k: int,
                   causal: bool, window: int | None, kv_len: int,
                   q_len: int, order: str) -> tuple[np.ndarray, int]:
    """Static (n_pairs, 4) int32 schedule of surviving (q-block, k-block)
    grid steps: columns are (iq, ik, first, last).

    ``order='row'`` (forward / dq): pairs grouped by q block, so the
    output o/dq block index is constant across consecutive steps and the
    online-softmax scratch drains exactly once per row.  ``order='col'``
    (dk/dv): grouped by k block.  first/last flag the group boundaries
    (accumulator init / drain).  Rows (and, in 'col' order, columns) with
    an empty band still get one fully-masked sentinel pair so every
    output block is initialized and drained — the mask guard inside the
    kernels zeroes its contribution.

    Returns (table, n_scheduled) where n_scheduled counts the REAL pairs
    (sentinels excluded) — the number the pruning benchmark reports.
    """
    rows: list[list[int]] = []
    n_real = 0
    for iq in range(nq):
        lo, hi = _row_range(iq, nk=nk, block_q=block_q, block_k=block_k,
                            causal=causal, window=window, kv_len=kv_len,
                            q_len=q_len)
        if hi < lo:
            rows.append([iq, 0, -1, -1])  # sentinel: fully masked
        else:
            n_real += hi - lo + 1
            for ik in range(lo, hi + 1):
                rows.append([iq, ik, 0, 0])
    if order == "col":
        by_col: dict[int, list[int]] = {ik: [] for ik in range(nk)}
        for iq, ik, s, _ in rows:
            if s != -1:
                by_col[ik].append(iq)
        rows = []
        for ik in range(nk):
            iqs = by_col[ik] or [nq - 1]   # sentinel for untouched columns
            for j, iq in enumerate(iqs):
                rows.append([iq, ik, int(j == 0), int(j == len(iqs) - 1)])
    else:
        assert order == "row", order
        out = []
        by_row: dict[int, list[list[int]]] = {}
        for r in rows:
            by_row.setdefault(r[0], []).append(r)
        for iq in range(nq):
            group = by_row[iq]
            for j, r in enumerate(group):
                out.append([r[0], max(r[1], 0), int(j == 0),
                            int(j == len(group) - 1)])
        rows = out
    table = np.asarray(rows, dtype=np.int32)
    return table, n_real


def scheduled_block_counts(Sq: int, Sk: int, *, block_q: int, block_k: int,
                           causal: bool, window: int | None
                           ) -> tuple[int, int]:
    """(scheduled, dense) k-block counts for one head's grid — the
    pruning win the benchmark reports (dense = nq * nk)."""
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    _, real = _pair_schedule(nq, nk, block_q, block_k, bool(causal),
                             window, Sk, Sq, "row")
    return real, nq * nk


# ---------------------------------------------------------------------------
# Forward kernel: online softmax, emits (o, lse)
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(sched_ref, offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                   window: int | None, block_q: int, block_k: int,
                   kv_len: int):
    p_id = pl.program_id(1)
    iq = sched_ref[p_id, 0]
    ik = sched_ref[p_id, 1]
    first = sched_ref[p_id, 2]
    last = sched_ref[p_id, 3]

    @pl.when(first == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (block_q, d)
    k = k_ref[0]                       # (block_k, d)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    loc_k = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    qpos = offs_ref[0] + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = offs_ref[1] + loc_k
    mask = loc_k < kv_len              # padded keys are never attended
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # guard: a fully-masked tile must not contribute exp(0)=1 weights
    # while the running max is still NEG_INF (the self-healing alpha only
    # erases them once a live tile arrives — which pruning may never
    # schedule for sentinel rows)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(last == 1)
    def _drain():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(safe)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _as_offs(q_offset, k_offset) -> jax.Array:
    return jnp.asarray(
        jnp.stack([jnp.asarray(q_offset), jnp.asarray(k_offset)]),
        jnp.int32)


def flash_attention_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                               causal: bool = True,
                               window: int | None = None,
                               block_q: int = 128, block_k: int = 128,
                               scale: float | None = None,
                               kv_len: int | None = None,
                               q_len: int | None = None,
                               q_offset=0, k_offset=0,
                               prune: bool = True,
                               interpret: bool = False
                               ) -> tuple[jax.Array, jax.Array]:
    """q: (BH, Sq, D); k, v: (BH_kv, Sk, D) with BH % BH_kv == 0 (GQA groups
    must be laid out so head h of q uses kv head h // (BH // BH_kv)).

    Returns ``(o, lse)`` with ``lse`` f32 (BH, Sq) — the flash residual.
    ``kv_len``/``q_len`` bound the VALID region when Sq/Sk carry padding;
    ``q_offset``/``k_offset`` (traced OK) shift the band mask to global
    positions for the ring's per-hop fold.  ``prune=True`` drops fully-
    masked k-blocks from the schedule (takes effect only when both
    offsets are statically zero — shifted bands use the dense grid)."""
    BH, Sq, Dh = q.shape
    BHkv, Sk, _ = k.shape
    assert BH % BHkv == 0, (BH, BHkv)
    group = BH // BHkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    kv_len = Sk if kv_len is None else kv_len
    q_len = Sq if q_len is None else q_len
    nq, nk = Sq // block_q, Sk // block_k
    # the pruned schedule is built in LOCAL positions — any nonzero (or
    # traced) offset shifts the band, so those calls get the dense grid
    # and rely on the in-kernel mask alone
    zero_offs = (isinstance(q_offset, int) and isinstance(k_offset, int)
                 and q_offset == 0 and k_offset == 0)
    if prune and zero_offs:
        sched, _ = _pair_schedule(nq, nk, block_q, block_k, bool(causal),
                                  window, kv_len, q_len, "row")
    else:
        sched, _ = _pair_schedule(nq, nk, block_q, block_k, False, None,
                                  kv_len, q_len, "row")
    n_pairs = sched.shape[0]

    kern = functools.partial(_fa_fwd_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q, block_k=block_k,
                             kv_len=kv_len)
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=(BH, n_pairs),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
            # K/V shared across the q-heads of a GQA group (zero derivative
            # of the kv index against the intra-group head axis).
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h // group, sr[p, 1], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h // group, sr[p, 1], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[_out_struct((BH, Sq, Dh), q.dtype, q, k, v),
                   _out_struct((BH, Sq, LANES), jnp.float32, q, k, v)],
        interpret=interpret,
    )(jnp.asarray(sched), _as_offs(q_offset, k_offset), q, k, v)
    return o, lse[..., 0]


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Forward-only entry (kept for benches/oracle sweeps); the trainable
    path is ``flash_attention_train``."""
    o, _ = flash_attention_fwd_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, scale=scale, interpret=interpret)
    return o


# ---------------------------------------------------------------------------
# Backward kernels: dq re-streams k-blocks, dk/dv re-stream q-blocks
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(sched_ref, offs_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, acc_ref, *, scale: float,
                      causal: bool, window: int | None, block_q: int,
                      block_k: int, kv_len: int):
    p_id = pl.program_id(1)
    iq = sched_ref[p_id, 0]
    ik = sched_ref[p_id, 1]

    @pl.when(sched_ref[p_id, 2] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                        # (block_q, d)
    k = k_ref[0]                        # (block_k, d)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    loc_k = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    qpos = offs_ref[0] + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = offs_ref[1] + loc_k
    mask = loc_k < kv_len
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    # p from the saved lse — the PSum re-stream.  The explicit mask guard
    # matters: a fully-masked row has lse == NEG_INF and exp(s - lse)
    # would resurrect masked entries as exp(0) = 1.
    p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), 0.0)

    do = do_ref[0].astype(jnp.float32)                 # (block_q, d)
    dp = jax.lax.dot_general(
        do, v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_q, block_k)
    ds = p * (dp - delta_ref[0][:, :1]) * scale
    acc_ref[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(sched_ref[p_id, 3] == 1)
    def _drain():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(sched_ref, offs_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       scale: float, causal: bool, window: int | None,
                       block_q: int, block_k: int, kv_len: int):
    p_id = pl.program_id(1)
    iq = sched_ref[p_id, 0]
    ik = sched_ref[p_id, 1]

    @pl.when(sched_ref[p_id, 2] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # Transposed frame: tiles are (block_k, block_q), so the per-q-row
    # lse/delta arrive lane-major, (1, block_q), and both accumulations
    # are plain matmuls: dv += p^T @ do, dk += ds^T @ q.
    k = k_ref[0].astype(jnp.float32)    # (block_k, d)
    v = v_ref[0].astype(jnp.float32)
    loc_k = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    qpos = offs_ref[0] + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    kpos = offs_ref[1] + loc_k
    mask = loc_k < kv_len
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window

    # the whole GQA group folds into this k column's accumulators (the
    # kv-stationary PSum: one accumulator per k block, q streams); one
    # q head at a time, as Mosaic contracts a single dim per matmul
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))
    dk = jnp.zeros(dk_acc.shape, jnp.float32)
    dv = jnp.zeros(dv_acc.shape, jnp.float32)
    for g in range(q_ref.shape[1]):
        q = q_ref[0, g].astype(jnp.float32)            # (block_q, d)
        do = do_ref[0, g].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(g, 1), :]               # (1, block_q)
        delta = delta_ref[0, pl.ds(g, 1), :]
        s_t = jax.lax.dot_general(
            k, q, nt, preferred_element_type=jnp.float32) * scale
        p_t = jnp.where(mask, jnp.exp(s_t - lse), 0.0)  # (block_k, block_q)
        dv += jax.lax.dot_general(p_t, do, nn,
                                  preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v, do, nt,
                                   preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta) * scale
        dk += jax.lax.dot_general(ds_t, q, nn,
                                  preferred_element_type=jnp.float32)
    dk_acc[...] += dk
    dv_acc[...] += dv

    @pl.when(sched_ref[p_id, 3] == 1)
    def _drain():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                               do: jax.Array, lse: jax.Array,
                               delta: jax.Array, *, causal: bool = True,
                               window: int | None = None, block_q: int = 128,
                               block_k: int = 128, scale: float | None = None,
                               kv_len: int | None = None,
                               q_len: int | None = None,
                               q_offset=0, k_offset=0, prune: bool = True,
                               interpret: bool = False
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash backward from the saved ``(lse, delta)`` residuals.

    q/do: (BH, Sq, D); k/v: (BHkv, Sk, D); lse/delta: f32 (BH, Sq) with
    ``delta = rowsum(do * o)``.  Returns (dq, dk, dv) in f32 (callers cast;
    the ring accumulates hops in f32).  Two kernels, two re-streams of the
    forward's schedule: dq holds q rows stationary against streaming
    k-blocks (row-ordered pair table), dk/dv hold k columns stationary
    against streaming q-blocks (column-ordered pair table, GQA group
    folded inside the tile so the kv accumulator sums its whole group)."""
    BH, Sq, Dh = q.shape
    BHkv, Sk, _ = k.shape
    assert BH % BHkv == 0, (BH, BHkv)
    group = BH // BHkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    kv_len = Sk if kv_len is None else kv_len
    q_len = Sq if q_len is None else q_len
    nq, nk = Sq // block_q, Sk // block_k
    # dense schedule unless offsets are statically zero (see fwd)
    zero_offs = (isinstance(q_offset, int) and isinstance(k_offset, int)
                 and q_offset == 0 and k_offset == 0)
    eff_causal = bool(causal) if (prune and zero_offs) else False
    eff_window = window if (prune and zero_offs) else None
    sched_row, _ = _pair_schedule(nq, nk, block_q, block_k, eff_causal,
                                  eff_window, kv_len, q_len, "row")
    sched_col, _ = _pair_schedule(nq, nk, block_q, block_k, eff_causal,
                                  eff_window, kv_len, q_len, "col")
    offs = _as_offs(q_offset, k_offset)
    f32 = jnp.float32

    kern_kw = dict(scale=scale, causal=causal, window=window,
                   block_q=block_q, block_k=block_k, kv_len=kv_len)

    dq_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=(BH, sched_row.shape[0]),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h // group, sr[p, 1], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h // group, sr[p, 1], 0)),
            pl.BlockSpec((1, block_q, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda h, p, sr, orf: (h, sr[p, 0], 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh),
                               lambda h, p, sr, orf: (h, sr[p, 0], 0)),
        scratch_shapes=[pltpu.VMEM((block_q, Dh), f32)],
    )
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **kern_kw),
        grid_spec=dq_spec,
        out_shape=_out_struct((BH, Sq, Dh), f32, q, k, v, do),
        interpret=interpret,
    )(jnp.asarray(sched_row), offs, q, k, v, do, _lanes(lse), _lanes(delta))

    # group-major views so one kv grid step sees its whole GQA group
    qg = q.reshape(BHkv, group, Sq, Dh)
    dog = do.reshape(BHkv, group, Sq, Dh)
    lseg = lse.reshape(BHkv, group, Sq)
    deltag = delta.reshape(BHkv, group, Sq)
    dkv_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=(BHkv, sched_col.shape[0]),
        in_specs=[
            pl.BlockSpec((1, group, block_q, Dh),
                         lambda h, p, sr, orf: (h, 0, sr[p, 0], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 1], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 1], 0)),
            pl.BlockSpec((1, group, block_q, Dh),
                         lambda h, p, sr, orf: (h, 0, sr[p, 0], 0)),
            pl.BlockSpec((1, group, block_q),
                         lambda h, p, sr, orf: (h, 0, sr[p, 0])),
            pl.BlockSpec((1, group, block_q),
                         lambda h, p, sr, orf: (h, 0, sr[p, 0])),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 1], 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda h, p, sr, orf: (h, sr[p, 1], 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, Dh), f32),
                        pltpu.VMEM((block_k, Dh), f32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **kern_kw),
        grid_spec=dkv_spec,
        out_shape=[_out_struct((BHkv, Sk, Dh), f32, q, k, v, do),
                   _out_struct((BHkv, Sk, Dh), f32, q, k, v, do)],
        interpret=interpret,
    )(jnp.asarray(sched_col), offs, qg, k, v, dog, lseg, deltag)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Trainable entry: fwd + bwd bound under one custom VJP
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlashSpec:
    """Static description of one trainable flash-attention call (hashable:
    it rides ``custom_vjp``'s nondiff_argnums)."""
    causal: bool
    window: int | None
    block_q: int
    block_k: int
    scale: float
    kv_len: int
    q_len: int
    prune: bool
    interpret: bool

    def kw(self) -> dict:
        return dict(causal=self.causal, window=self.window,
                    block_q=self.block_q, block_k=self.block_k,
                    scale=self.scale, kv_len=self.kv_len, q_len=self.q_len,
                    prune=self.prune, interpret=self.interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def flash_attention_train(spec: FlashSpec, q, k, v):
    """Differentiable fused flash attention: q (BH, Sq, D), k/v (BHkv, Sk,
    D).  Forward saves only (o, lse), named by :func:`name_residuals`;
    backward is the two Pallas re-stream kernels above — the default
    trainable attention path on TPU."""
    o, _ = flash_attention_fwd_pallas(q, k, v, **spec.kw())
    return o


def _flash_train_fwd(spec: FlashSpec, q, k, v):
    o, lse = name_residuals(*flash_attention_fwd_pallas(q, k, v,
                                                        **spec.kw()))
    return o, (q, k, v, o, lse)


def _flash_train_bwd(spec: FlashSpec, res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_attention_bwd_pallas(q, k, v, do, lse, delta,
                                            **spec.kw())
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_train.defvjp(_flash_train_fwd, _flash_train_bwd)


# ---------------------------------------------------------------------------
# Decode: one new token against a KV cache (the decode_* / long_* shapes).
# ---------------------------------------------------------------------------

def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, block_k: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (nq, d) — the group's q heads
    k = k_ref[0]                       # (block_k, d)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (nq, block_k)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < len_ref[0], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _drain():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_decode_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        lengths: jax.Array, *, block_k: int = 512,
                        scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (B*Hkv, group, D) one token per sequence, grouped by kv head;
    k_cache/v_cache: (B*Hkv, S, D); lengths: (B*Hkv,) valid cache lengths.
    Returns (B*Hkv, group, D).

    ``block_k`` is a ceiling, not a contract: when the cache length is not
    a multiple (short caches, odd bucket sizes), the block clamps to the
    pow2 floor of S and the cache pads to the next block multiple — padded
    positions sit at >= S >= lengths, so the length mask drops them."""
    BH, G, Dh = q.shape
    BH2, S, _ = k_cache.shape
    assert BH == BH2, (q.shape, k_cache.shape)
    if S % block_k != 0:
        block_k = min(block_k, pow2_floor(S))
        Sp = -(-S // block_k) * block_k
        pad = [(0, 0), (0, Sp - S), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        S = Sp
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    grid = (BH, S // block_k)
    kern = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda h, ik: (h, 0, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda h, ik: (h, ik, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda h, ik: (h, ik, 0)),
            pl.BlockSpec((1,), lambda h, ik: (h,)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda h, ik: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_cache, v_cache, lengths)

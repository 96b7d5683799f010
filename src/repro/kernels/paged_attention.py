"""Paged flash-decode Pallas kernel: page-table-gathered KV attention.

The serving twin of ``attention._decode_kernel``: instead of a dense
(B, S, Hkv, Dh) cache, K/V live in a global POOL of fixed-size pages and a
per-slot page table says which physical pages hold a slot's history.  The
page table is a scalar-prefetch operand (``compat.prefetch_scalar_grid_spec``)
so the K/V BlockSpec index maps chase it *inside the grid* — the gather is
pure DMA scheduling, no materialized contiguous copy.  This is the paper's
exchange-mesh move at serving scale: small local tiles (pages) promoted to
global visibility through an index fabric instead of dense reservation.

Grid: (B*Hkv, n_pages_per_slot); page j of slot b streams through VMEM
while the online-softmax accumulator for that slot/kv-head group stays
stationary — identical schedule to the dense decode kernel, only the
kv-block address is indirected.

The int8 path keeps the pool quantized in HBM and dequantizes one page at
a time inside the kernel, so quantized serving never materializes an f32
cache.  The per-(token, head) scales ride along page-major, one
``(Hkv, page_size)`` block per page: Mosaic tiles the last two block dims
by (8, 128) unless they span the whole array dims, which a per-head
``(1, page_size)`` slice would not.  The kernel picks its head's row and
applies it along the key (lane) axis of the score and probability tiles —
``(q . k) * s_k`` and ``(p * s_v) @ v`` equal the dequantized products.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import compat

NEG_INF = -1e30  # avoid nan from (-inf) - (-inf)


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, page_size: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # flat grid axis 0 = b * Hkv + h; lengths are replicated per kv head by
    # the wrapper so len_ref indexes directly by the flat id.
    b = pl.program_id(0)
    k = k_ref[0, 0].astype(jnp.float32)     # (page_size, d)
    v = v_ref[0, 0].astype(jnp.float32)
    q = q_ref[0]                            # (group, d)
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (group, page_size)
    if quantized:
        hk = b % ks_ref.shape[1]
        s = s * ks_ref[0, pl.ds(hk, 1), :]             # (1, page_size)
    kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < len_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    if quantized:
        p = p * vs_ref[0, pl.ds(hk, 1), :]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _drain():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def paged_flash_decode_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_table: jax.Array,
                              lengths: jax.Array,
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None, *,
                              page_size: int,
                              scale: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """q: (B*Hkv, group, D) one token per slot, grouped by kv head;
    k_pages/v_pages: (Hkv, P, page_size, D) global pools; page_table:
    (B*Hkv, max_pages) physical ids (page 0 = trash, masked by length);
    lengths: (B*Hkv,) valid cached tokens (>= 1: page 0 of every live slot
    covers position 0, so the first grid step is never fully masked).
    Scales (int8 pools): page-major (P, Hkv, page_size) f32.  Returns
    (B*Hkv, group, D).  The wrapper (kernels/ops.py) replicates per-slot
    tables/lengths across kv heads so grid axis 0 is flat (b, kv head)."""
    BH, G, Dh = q.shape
    Hkv, P, pg, _ = k_pages.shape
    assert pg == page_size, (pg, page_size)
    assert BH % Hkv == 0, (BH, Hkv)
    MP = page_table.shape[1]
    quantized = k_scale is not None
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    grid = (BH, MP)

    def kv_spec():
        # page indirection: block index for the page axis comes from the
        # prefetched table, the kv-head axis from the flat grid id.
        return pl.BlockSpec(
            (1, 1, page_size, Dh),
            lambda h, j, pt_ref, len_ref: (h % Hkv, pt_ref[h, j], 0, 0))

    def scale_spec():
        # every kv head's scales of the table's page (see module doc)
        return pl.BlockSpec(
            (1, Hkv, page_size),
            lambda h, j, pt_ref, len_ref: (pt_ref[h, j], 0, 0))

    in_specs = [
        pl.BlockSpec((1, G, Dh), lambda h, j, pt_ref, len_ref: (h, 0, 0)),
        kv_spec(),
        kv_spec(),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        in_specs += [scale_spec(), scale_spec()]
        operands += [k_scale, v_scale]

    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Dh),
                               lambda h, j, pt_ref, len_ref: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             page_size=page_size, quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, G, Dh), q.dtype),
        interpret=interpret,
    )(page_table, lengths, *operands)

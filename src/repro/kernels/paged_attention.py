"""Paged flash-decode Pallas kernel: page-table-gathered KV attention.

The serving twin of ``attention._decode_kernel``: instead of a dense
(B, S, Hkv, Dh) cache, K/V live in a global POOL of fixed-size pages and a
per-slot page table says which physical pages hold a slot's history.  The
page table and the lengths are scalar-prefetch operands
(``compat.prefetch_scalar_grid_spec``), so the pages' index maps chase the
table *inside the grid* — the gather is pure DMA scheduling, no
materialized contiguous copy.  This is the paper's exchange-mesh move at
serving scale: small local tiles (pages) promoted to global visibility
through an index fabric instead of dense reservation.

Grid: (B, n_blocks); a block is ``ppb`` consecutive table entries.  A step
attends all H query heads of slot b against one block of pages of all Hkv
kv heads, a static loop over the heads, while the online-softmax state
``(m, l, acc)`` of the slot's H rows stays in VMEM scratch across the
block axis.  The pool is read in place, in the layout the serving step
stores it, ``(L, P, page, Hkv, Dh)`` for all L layers: the layer is a
third scalar-prefetch operand, and each page of the block is one operand
whose index map is (layer, table entry), so one page of every kv head is
one contiguous copy and nothing the size of a layer's pool or the table
is sliced, transposed or repeated per call.  The pipeline double-buffers
those copies across grid steps.

Bound by length: a block whose first position is at or past the slot's
length does no compute, and its index maps name the block the pipeline
holds next anyway — the next slot's first block (the last slot: its own
last live block) — so it copies nothing new and the next slot's first
copy overlaps this slot's last compute.  Inside the last live block the
``kpos < len`` mask drops the tail; table columns past the view (``MP`` no
multiple of ``ppb``) re-read the view's last page, which the mask drops.
Copies are issued by the pipeline rather than by hand: Mosaic refuses a
hand-made copy of one page whose minor dims are padded in HBM (Dh = 64,
or the (page, Hkv) scales), and a block spec over whole trailing dims
serves every head shape.

``pages_per_block`` derives from the page's bytes (about ``BLOCK_BYTES``
of K a block, at least one page, at most the view), so every head shape
gets its block from the same rule.

The int8 path keeps the pool quantized in HBM and dequantizes one block
at a time inside the kernel, so quantized serving never materializes an
f32 cache.  Scales ride in their stored ``(L, P, page, Hkv)`` layout, one
operand per page beside the page's K or V; a head's scales scale the rows
of its K and V tiles, which equals the dequantized products.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import compat

NEG_INF = -1e30  # avoid nan from (-inf) - (-inf)
# K bytes one grid step aims to copy; K and V double-buffered hold four
# times that in VMEM, well under the default scoped limit
BLOCK_BYTES = 256 * 1024


def pages_per_block(page_size: int, n_kv_heads: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages one grid step copies: about ``BLOCK_BYTES`` of K, at least
    one page and at most the view."""
    page_bytes = page_size * n_kv_heads * head_dim * itemsize
    return max(1, min(BLOCK_BYTES // page_bytes, max_pages))


def _paged_decode_kernel(pt_ref, len_ref, layer_ref, q_ref, *refs,
                         scale: float, ppb: int, quantized: bool):
    del layer_ref                       # read by the index maps only
    n = 4 if quantized else 2
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    ks_refs = vs_refs = None
    if quantized:
        ks_refs, vs_refs = refs[2 * ppb:3 * ppb], refs[3 * ppb:4 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[n * ppb:]
    b, i = pl.program_id(0), pl.program_id(1)
    _, page_size, Hkv, Dh = k_refs[0].shape
    G = q_ref.shape[2]
    block_tokens = ppb * page_size

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def load(refs):
        """The block's pages (or scales), each read from VMEM once, the
        leading unit dim dropped."""
        return [lax.reshape(r[...], r.shape[1:]) for r in refs]

    def head_rows(pages, scales, h):
        """(block_tokens, Dh) f32 rows of kv head h across the block.
        ``lax`` slices, not indexing: the loops over heads and pages are
        traced into every decode step program, so each op costs set-up."""
        x = jnp.concatenate(
            [lax.index_in_dim(p, h, axis=1, keepdims=False) for p in pages])
        x = x.astype(jnp.float32)
        if scales is not None:
            x = x * jnp.concatenate(
                [lax.slice_in_dim(s, h, h + 1, axis=1) for s in scales])
        return x

    @pl.when(i * block_tokens < len_ref[b])
    def _step():
        ks = vs = None
        kp, vp = load(k_refs), load(v_refs)
        if quantized:
            ks, vs = load(ks_refs), load(vs_refs)
        kpos = i * block_tokens + lax.broadcasted_iota(
            jnp.int32, (G, block_tokens), 1)
        keep = kpos < len_ref[b]
        q = lax.reshape(q_ref[...], q_ref.shape[1:]).astype(jnp.float32)
        m_all, l_all, acc_all = m_ref[...], l_ref[...], acc_ref[...]
        out = []
        for h in range(Hkv):
            m_prev, l_prev, acc = (lax.index_in_dim(x, h, keepdims=False)
                                   for x in (m_all, l_all, acc_all))
            s = lax.dot_general(
                lax.index_in_dim(q, h, keepdims=False),          # (G, Dh)
                head_rows(kp, ks, h),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, T)
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            acc = acc * alpha + lax.dot_general(
                p, head_rows(vp, vs, h),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_prev * alpha + p.sum(axis=-1, keepdims=True),
                        acc))
        m_new, l_new, acc = zip(*out)
        m_ref[...] = jnp.stack(m_new)
        l_ref[...] = jnp.stack(l_new)
        acc_ref[...] = jnp.stack(acc)

    @pl.when(i == pl.num_programs(1) - 1)
    def _drain():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe)[None].astype(o_ref.dtype)


def paged_flash_decode_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_table: jax.Array,
                              lengths: jax.Array, layer: jax.Array,
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None, *,
                              pages_per_block: int,
                              scale: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one token per slot, query heads grouped by kv head;
    k_pages/v_pages: (L, P, page_size, Hkv, D) global pools of all layers
    as stored; page_table: (B, max_pages) physical ids (page 0 = trash,
    masked by length); lengths: (B,) valid cached tokens (>= 1: page 0 of
    every live slot covers position 0, so a slot's first block is never
    fully masked); layer: int32 scalar, the layer attended.  Scales (int8
    pools): (L, P, page_size, Hkv) f32.  Returns (B, H, D)."""
    B, H, Dh = q.shape
    _, P, page_size, Hkv, _ = k_pages.shape
    assert H % Hkv == 0, (H, Hkv)
    MP = page_table.shape[1]
    ppb = pages_per_block
    n_blocks = pl.cdiv(MP, ppb)
    block_tokens = ppb * page_size
    quantized = k_scale is not None
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    def page_of(j, n_dims):
        """Index map of the block's j-th page of the layer.  A block past
        the slot's length names the next slot's first block instead (the
        last slot: its own last live block), so the pipeline copies
        nothing for it and the next slot's first copy overlaps this slot's
        last compute.  Columns past the view re-read the view's last page
        (masked)."""
        def index(b, i, pt, ln, lyr):
            # lax, not jnp: these run once per page operand at trace time
            last = lax.div(lax.max(lax.sub(ln[b], 1), 0), block_tokens)
            ahead = lax.bitwise_and(lax.gt(i, last), lax.lt(b, B - 1))
            row = lax.select(ahead, lax.add(b, 1), b)
            blk = lax.select(ahead, 0, lax.min(i, last))
            col = lax.min(lax.add(lax.mul(blk, ppb), j), MP - 1)
            return (lyr[0], pt[row, col]) + (0,) * (n_dims - 2)
        return index

    G = H // Hkv
    q_spec = pl.BlockSpec((1, Hkv, G, Dh),
                          lambda b, i, pt, ln, lyr: (b, 0, 0, 0))
    in_specs = [q_spec]
    # the layer dim is squeezed: the kernel sees (1, page, Hkv, Dh) pages
    in_specs += [pl.BlockSpec((None, 1, page_size, Hkv, Dh), page_of(j, 5))
                 for j in range(ppb)] * 2
    operands = [q.reshape(B, Hkv, G, Dh)] + [k_pages] * ppb + [v_pages] * ppb
    if quantized:
        in_specs += [pl.BlockSpec((None, 1, page_size, Hkv), page_of(j, 4))
                     for j in range(ppb)] * 2
        operands += [k_scale] * ppb + [v_scale] * ppb
    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),       # m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),       # l
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),      # acc
        ],
    )
    kern = functools.partial(_paged_decode_kernel, scale=scale, ppb=ppb,
                             quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=interpret,
    )(page_table, lengths, jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands).reshape(B, H, Dh)

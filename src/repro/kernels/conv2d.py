"""Direct 2D convolution Pallas kernel (paper Eq. 2, incl. stride/dilation).

NHWC x HWIO -> NHWC. The TEU tile maps to (a block of output rows) x (all
columns) x (a block of output channels); the overlapping input window — the
operand the FIFO mesh shares between neighbouring tiles in Fig. 2 — is
expressed with an element-indexed halo block (``compat.element_block_spec``
over ``pl.Element`` dims), and is REUSED across all
co-blocks because the grid order puts `co` innermost of the parallel dims
(the block's index map is invariant to `co`, so Mosaic keeps it VMEM-resident
— the intra-chip analogue of sharing E between P and Q). The reduction
(ci, kh, kw) runs inside the kernel body (temporal indices of Eq. 2), keeping
the f32 PSum block stationary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import compat


def _conv_kernel(x_ref, w_ref, o_ref, *, stride: int, dilation: int,
                 kh: int, kw: int):
    # x_ref: (1, ih_blk, iw_pad, ci)  w_ref: (kh, kw, ci, bco)
    # o_ref: (1, block_oh, ow, bco)
    x = x_ref[0]
    block_oh, ow = o_ref.shape[1], o_ref.shape[2]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for m in range(kh):
        for n in range(kw):
            win = jax.lax.slice(
                x,
                (m * dilation, n * dilation, 0),
                (m * dilation + (block_oh - 1) * stride + 1,
                 n * dilation + (ow - 1) * stride + 1,
                 x.shape[2]),
                (stride, stride, 1),
            )  # (block_oh, ow, ci)
            acc += jax.lax.dot_general(
                win, w_ref[m, n],
                dimension_numbers=(((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def conv2d_pallas(x: jax.Array, w: jax.Array, *, stride: int = 1,
                  dilation: int = 1, block_oh: int = 8, block_co: int = 128,
                  interpret: bool = False) -> jax.Array:
    """x: (N, IH, IW, CI), w: (KH, KW, CI, CO) -> (N, OH, OW, CO). VALID pad.

    OH must be a multiple of block_oh and CO of block_co (ops.py pads).
    """
    N, IH, IW, CI = x.shape
    KH, KW, CI2, CO = w.shape
    assert CI == CI2, (x.shape, w.shape)
    OH = (IH - (KH - 1) * dilation - 1) // stride + 1
    OW = (IW - (KW - 1) * dilation - 1) // stride + 1
    assert OH % block_oh == 0, (OH, block_oh)
    assert CO % block_co == 0, (CO, block_co)

    # halo window of input rows feeding one block of output rows
    ih_blk = (block_oh - 1) * stride + (KH - 1) * dilation + 1
    grid = (N, OH // block_oh, CO // block_co)
    kern = functools.partial(_conv_kernel, stride=stride, dilation=dilation,
                             kh=KH, kw=KW)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # Element-indexed rows: overlapping halo blocks; invariant to c.
            compat.element_block_spec(
                (1, compat.Element(ih_blk), IW, CI),
                lambda n, y, c: (n, y * block_oh * stride, 0, 0)),
            pl.BlockSpec((KH, KW, CI, block_co), lambda n, y, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, block_oh, OW, block_co),
                               lambda n, y, c: (n, y, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, CO), x.dtype),
        interpret=interpret,
    )(x, w)

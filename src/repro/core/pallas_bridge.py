"""Bridge: VectorMesh tile schedules -> Pallas BlockSpecs (TPU adaptation).

The paper's TEU schedule becomes one Pallas grid step: operand tiles live in
VMEM, the PSum buffer is an f32 VMEM accumulator, and the BFN conflict-free
condition becomes (sublane, lane) = (8, 128) alignment of the block shapes.
The grid order comes from ``core.exchange.order_grid_for_sharing`` so blocks
invariant along the innermost grid dims stay VMEM-resident (the intra-chip
FIFO analogue).

Both searches resolve through the vectorized + memoized scheduler engine
(``repro.core.autotune``), so ``plan_kernel`` for a repeated op shape (e.g.
every decoder layer of an LM calling ``matmul_block_shapes`` with the same
M/N/K) is a cache lookup, not a lattice scan.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Mapping

from .ndrange import TensorOp
from .tiling import BufferSpec, TileSchedule, search_tiles
from .exchange import order_grid_for_sharing, GridOrder

# TPU tiling quanta for the last two axes of a VMEM block (fp32/bf16).
SUBLANE = 8
LANE = 128
MXU = 128


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


def aligned(x: int, m: int) -> bool:
    return x % m == 0


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Everything a Pallas kernel needs: block shapes, grid, order."""

    schedule: TileSchedule
    grid_order: GridOrder
    block: dict[str, int]          # tile sizes, TPU-aligned
    grid: tuple[int, ...]          # grid extents in grid_order
    dims_order: tuple[str, ...]

    @property
    def vmem_bytes(self) -> int:
        return self.schedule.input_bytes + self.schedule.psum_bytes


def plan_kernel(op: TensorOp, *, vmem_budget_bytes: int = 64 * 1024 * 1024,
                psum_budget_bytes: int = 32 * 1024 * 1024,
                align: Mapping[str, int] | None = None,
                caps: Mapping[str, int] | None = None) -> KernelPlan:
    """Run the paper's tile search with TPU constraints and order the grid.

    ``align`` maps NDRange dim name -> required multiple (e.g. the two matmul
    lanes -> 128 for the MXU). Dims equal to their full size are exempt
    (ragged final blocks are handled by masking in the kernels).
    """
    buf = BufferSpec(input_bytes=vmem_budget_bytes,
                     psum_bytes=psum_budget_bytes,
                     align=dict(align or {}),
                     lanes=MXU * MXU)
    sched = search_tiles(op, buf, caps=caps)
    order = order_grid_for_sharing(op, sched.tile)
    grid_shape = op.grid_shape(sched.tile)
    grid = tuple(grid_shape[name] for name in order.order)
    return KernelPlan(schedule=sched, grid_order=order, block=dict(sched.tile),
                      grid=grid, dims_order=order.order)


# the attention tile search's VMEM budget for 2-byte operands; wider
# operands get a proportionally smaller one, so the flash backward's dk/dv
# kernel (a GQA group of q and do blocks plus four score-sized f32 tiles)
# stays inside Mosaic's 16 MiB of scoped VMEM: float32 at the 4 MiB blocks
# (512, 1024) asked 17.07 MiB on a v5e
ATTN_VMEM_BUDGET = 4 * 1024 * 1024


@functools.lru_cache(maxsize=4096)
def attention_block_shapes(q_len: int, kv_len: int, head_dim: int,
                           *, itemsize: int = 2) -> tuple[int, int]:
    """(block_q, block_k) for a flash-attention score tile, TPU-aligned.

    Runs the paper's tile search on the QK^T NDRange (head dim is the
    temporal/streamed axis, q and s are the stationary PSum axes) instead
    of hard-coding 128s: the per-shape result is memoized here AND behind
    the scheduler engine's structural-key cache, so every decoder layer of
    an LM resolves its blocks with a dict lookup.  Blocks clamp to
    [SUBLANE, 512] x [LANE, 1024] and to the (padded) problem size —
    the flash kernels pad ragged tails and mask them via kv_len/q_len.
    ``itemsize`` is the operands' width in bytes; it scales the VMEM
    budget (``ATTN_VMEM_BUDGET`` at 2 bytes)."""
    from .ndrange import attention_scores_op
    vmem_budget_bytes = ATTN_VMEM_BUDGET * 2 // max(2, itemsize)
    q_cap = max(SUBLANE, min(512, q_len))
    k_cap = max(LANE if kv_len >= LANE else pow2_floor(kv_len),
                min(1024, kv_len))
    op = attention_scores_op(1, max(q_len, SUBLANE), max(kv_len, 1),
                             head_dim)
    plan = plan_kernel(
        op,
        vmem_budget_bytes=vmem_budget_bytes,
        psum_budget_bytes=vmem_budget_bytes // 2,
        align={"q": SUBLANE if q_len >= SUBLANE else 1,
               "s": LANE if kv_len >= LANE else 1},
        caps={"h": 1, "q": q_cap, "s": k_cap},
    )
    bq = max(1, min(plan.block["q"], q_cap))
    bk = max(1, min(plan.block["s"], k_cap))
    return bq, bk


def matmul_block_shapes(M: int, N: int, K: int,
                        *, vmem_budget_bytes: int = 8 * 1024 * 1024
                        ) -> tuple[int, int, int]:
    """Convenience: (bm, bn, bk) for an MxK @ KxN matmul, MXU-aligned.

    Uses the paper objective ((bm+bn)*bk bytes per bm*bn*bk MACs) under the
    VMEM budget; clamps to the problem size and rounds to MXU quanta.
    """
    from .ndrange import matmul_op
    op = matmul_op(M, N, K)
    plan = plan_kernel(
        op,
        vmem_budget_bytes=vmem_budget_bytes,
        psum_budget_bytes=vmem_budget_bytes // 2,
        align={"i": MXU if M >= MXU else 1,
               "j": LANE if N >= LANE else 1,
               "k": LANE if K >= LANE else 1},
    )
    b = plan.block
    return b["i"], b["j"], b["k"]

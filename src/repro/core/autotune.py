"""Scheduler engine: vectorized, pruned, memoized tile search + exchange
planning (paper §II-B, Fig. 2 — fast path).

Everything this repo derives from the paper — the Table III traffic numbers,
the Fig. 3/4 rooflines, and the Pallas ``plan_kernel``
block shapes — funnels through two brute-force searches: the §II-B tile
search (``core.tiling.search_tiles``) and the Fig. 2 grid-order search
(``core.exchange.order_grid_for_sharing``).  The reference implementations
walk the candidate lattice tile-object-by-tile-object in pure Python
(~28k dict candidates and ~0.3 s for one ResNet conv layer) and are re-run
for every (arch, workload) pair the simulator touches.

This module replaces those hot paths with three composable layers:

1. **Vectorized candidate evaluation** (``_search_tiles_vectorized``).
   The pow2 tile lattice is materialized as NumPy arrays.  Each operand
   axis is an affine expression whose footprint extent over a tile box is
   ``1 + sum_i |c_i| (t_i - 1)`` — affine in the tile sizes — so per-axis
   extents, operand footprints, PSum elems, MACs and bytes-per-MAC for
   *all* candidates are computed by broadcasting, never by per-tile
   ``AffineExpr`` object traversal.

2. **Admissibility pruning** (branch-and-bound on the partial product).
   Footprints are monotone nondecreasing in every tile dim, so while the
   lattice is built up dim-by-dim, any partial assignment whose footprint
   *lower bound* (remaining dims at their minimum, 1) already violates a
   buffer capacity is dropped — together with the entire sublattice
   hanging off it.  Conv-style 6-dim ops never touch the full cartesian
   product.  Per-dim candidate values are pre-capped the same way.

3. **Memoization** (``_memo``).  Results are
   keyed by a *structural* op signature (dim sizes/kinds, affine coeffs,
   bytes-per-elem, macs-per-point — NOT the op name) plus the BufferSpec /
   caps / mesh arguments, in a process-wide LRU.  ``search_tiles``,
   ``plan_mesh_exchange``, ``order_grid_for_sharing`` and (transitively)
   ``pallas_bridge.plan_kernel`` all share it, so the simulator's repeated
   searches across PE sweeps are free after the first.

The engine is *provably* result-identical to the reference brute force: it
draws candidates from the same ``ndrange.tile_candidates`` lattice, keeps
them in the same iteration order (first-minimum wins on ties, like the
reference ``<`` scan), evaluates the same objective ``(bytes_per_mac,
-temporal_coverage, -macs)``, and builds the winning ``TileSchedule``
through the same ``schedule_for`` constructor.  ``tests/test_autotune.py``
asserts equality against the reference on all five op families.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from collections import OrderedDict
from typing import Any, Callable, Mapping

import numpy as np

from .ndrange import TensorOp, tile_candidates

# ---------------------------------------------------------------------------
# Structural signatures (cache keys).
# ---------------------------------------------------------------------------

# Python ints are exact at any size; the vectorized path works in int64 and
# divides via float64 (which loses the correctly-rounded int/int semantics
# past 2**53).  Fall back to the reference scan when any full-tile quantity
# could exceed that, so the engine stays bit-identical to the brute force.
_INT64_SAFE = 2 ** 53


def op_signature(op: TensorOp) -> tuple:
    """Canonical *structural* identity of a TensorOp — everything that
    affects scheduling, excluding the display name.  Two ops built
    separately with identical dims/kinds/affine maps/dtypes hash equal and
    share cache entries."""
    return (
        tuple((d.name, d.size, d.kind) for d in op.dims),
        tuple((v.index_exprs, v.bytes_per_elem) for v in op.inputs),
        (op.output.index_exprs, op.output.bytes_per_elem),
        op.macs_per_point,
    )


def _buf_signature(buf) -> tuple:
    # `lanes` feeds the perf model, not the search — excluded on purpose so
    # e.g. a 128-PE and 512-PE arch with equal buffers share one entry.
    return (buf.input_bytes, buf.psum_bytes, buf.psum_bytes_per_elem,
            tuple(sorted(buf.align.items())))


def _caps_signature(caps: Mapping[str, int] | None) -> tuple:
    return tuple(sorted((caps or {}).items()))


# ---------------------------------------------------------------------------
# Layer 3: memoization (in-process LRU).
# ---------------------------------------------------------------------------

_LRU_MAXSIZE = 4096
_lru: OrderedDict[tuple, Any] = OrderedDict()
_lru_lock = threading.Lock()
cache_stats = {"hits": 0, "misses": 0, "evictions": 0}


def clear_cache() -> None:
    """Drop every memoized schedule/plan.  ``cache_stats`` counters
    survive — they are lifetime telemetry, not cache contents."""
    with _lru_lock:
        _lru.clear()


def _memo(key: tuple, compute: Callable[[], Any]) -> Any:
    """LRU lookup around ``compute()``."""
    with _lru_lock:
        if key in _lru:
            _lru.move_to_end(key)
            cache_stats["hits"] += 1
            return _lru[key]
    cache_stats["misses"] += 1
    value = compute()
    with _lru_lock:
        _lru[key] = value
        while len(_lru) > _LRU_MAXSIZE:
            _lru.popitem(last=False)
            cache_stats["evictions"] += 1
    return value


# ---------------------------------------------------------------------------
# Layers 1+2: vectorized lattice evaluation with branch-and-bound pruning.
# ---------------------------------------------------------------------------

def _lattice_overflow_risk(op: TensorOp) -> bool:
    full = op.full_tile()
    worst = op.tile_macs(full) + op.tile_input_bytes(full)
    worst += op.tile_psum_elems(full) + op.num_tiles(full)
    return worst >= _INT64_SAFE


def _build_pruned_lattice(op: TensorOp, buf, caps, pow2=True):
    """Materialize admissible tile candidates as an (N, n_dims) int64 array.

    Processes dims left-to-right (the ``itertools.product`` nesting order),
    carrying per-operand-axis extents; after each dim the *lower bound* of
    input bytes / PSum elems (remaining dims at tile=1 contribute nothing to
    any extent) is checked against the buffer and violating rows — whole
    sublattices of the remaining dims — are dropped.  Row order stays the
    product order, which is what makes first-minimum tie-breaking identical
    to the reference scan.
    """
    axes = tile_candidates(op, caps=caps, pow2=pow2)
    names = [d.name for d in op.dims]
    in_exprs = [(v.bytes_per_elem, e) for v in op.inputs for e in v.index_exprs]
    in_starts = []  # slices of in_exprs per input operand
    i = 0
    for v in op.inputs:
        in_starts.append((i, i + len(v.index_exprs)))
        i += len(v.index_exprs)
    out_exprs = list(op.output.index_exprs)

    # Per-dim pre-cap (cheap first pruning pass): a candidate value t for dim
    # d is admissible only if the footprint with every other dim at 1 fits.
    for j, d in enumerate(op.dims):
        kept = []
        for t in axes[j]:
            in_b = sum(
                v.bytes_per_elem * math.prod(
                    1 + abs(e.coeff(d.name)) * (t - 1)
                    for e in v.index_exprs)
                for v in op.inputs)
            ps = math.prod(1 + abs(e.coeff(d.name)) * (t - 1)
                           for e in out_exprs)
            if in_b <= buf.input_bytes and \
                    ps * buf.psum_bytes_per_elem <= buf.psum_bytes:
                kept.append(t)
            else:
                break  # monotone in t: larger values violate too
        axes[j] = kept or axes[j][:1]  # keep t=1 so infeasibility is reported
                                       # by the final mask, as in the reference

    tiles = np.zeros((1, 0), dtype=np.int64)
    exts = np.ones((1, len(in_exprs)), dtype=np.int64)   # input-axis extents
    pexts = np.ones((1, len(out_exprs)), dtype=np.int64)  # psum-axis extents
    for j, d in enumerate(op.dims):
        vals = np.asarray(axes[j], dtype=np.int64)
        n_old, n_v = tiles.shape[0], vals.shape[0]
        # old-major, vals-minor ravel == itertools.product order
        tiles = np.concatenate(
            [np.repeat(tiles, n_v, axis=0),
             np.tile(vals, n_old)[:, None]], axis=1)
        ic = np.array([abs(e.coeff(d.name)) for _, e in in_exprs],
                      dtype=np.int64)
        oc = np.array([abs(e.coeff(d.name)) for e in out_exprs],
                      dtype=np.int64)
        exts = (np.repeat(exts, n_v, axis=0)
                + ic[None, :] * (np.tile(vals, n_old)[:, None] - 1))
        pexts = (np.repeat(pexts, n_v, axis=0)
                 + oc[None, :] * (np.tile(vals, n_old)[:, None] - 1))
        # Branch-and-bound: lower-bound footprints with remaining dims at 1.
        in_lb = np.zeros(tiles.shape[0], dtype=np.int64)
        for (s, t), v in zip(in_starts, op.inputs):
            in_lb += exts[:, s:t].prod(axis=1) * v.bytes_per_elem
        ps_lb = pexts.prod(axis=1) * buf.psum_bytes_per_elem
        alive = (in_lb <= buf.input_bytes) & (ps_lb <= buf.psum_bytes)
        if j == len(op.dims) - 1 or not alive.all():
            # Always keep at least the all-ones row so the infeasible case
            # falls through to the final mask and raises like the reference.
            if not alive.any():
                alive = alive.copy()
                alive[0] = True
            tiles, exts, pexts = tiles[alive], exts[alive], pexts[alive]
        if j == len(op.dims) - 1:
            in_bytes, psum_elems = in_lb[alive], pexts.prod(axis=1)
    if tiles.shape[1] == 0:  # op with no dims (degenerate)
        in_bytes = np.zeros(1, dtype=np.int64)
        psum_elems = np.ones(1, dtype=np.int64)
    return names, tiles, in_bytes, psum_elems


def _search_tiles_vectorized(op: TensorOp, buf, caps, prefer_large: bool):
    """Vectorized replica of the reference ``search_tiles`` scan."""
    from .tiling import schedule_for  # local import: tiling imports us lazily

    names, tiles, in_bytes, psum_elems = _build_pruned_lattice(
        op, buf, caps)
    sizes = np.array([op.dim_map[n].size for n in names], dtype=np.int64)

    macs = tiles.prod(axis=1) * op.macs_per_point
    valid = (in_bytes <= buf.input_bytes) & \
            (psum_elems * buf.psum_bytes_per_elem <= buf.psum_bytes)
    for j, n in enumerate(names):
        a = buf.align.get(n)
        if a and a > 1:
            valid &= (tiles[:, j] % a == 0) | (tiles[:, j] == sizes[j])
    if not valid.any():
        raise ValueError(
            f"no tile of {op.name} fits buffers "
            f"(input<= {buf.input_bytes}B, psum<={buf.psum_bytes}B)")

    # Objective, staged exactly like the reference tuple comparison
    # (bytes_per_mac, -temporal_cov, -macs): exact-equality filtering per
    # stage == lexicographic min with first-occurrence tie-break.
    bpm = in_bytes / np.maximum(1, macs)          # float64, same rounding
    tcov = np.ones(tiles.shape[0])
    for j, n in enumerate(names):
        if op.dim_map[n].kind == "temporal":
            # same per-dim division + left-to-right product as math.prod
            tcov = tcov * (tiles[:, j] / sizes[j])

    mask = valid.copy()
    bpm_min = bpm[mask].min()
    mask &= bpm == bpm_min
    tc_max = tcov[mask].max()
    mask &= tcov == tc_max
    m_best = macs[mask].max() if prefer_large else macs[mask].min()
    mask &= macs == m_best
    idx = int(np.flatnonzero(mask)[0])
    tile = {n: int(tiles[idx, j]) for j, n in enumerate(names)}
    return schedule_for(op, tile)


# ---------------------------------------------------------------------------
# Public engine entry points (wired behind the core.tiling / core.exchange
# wrappers; call these directly for explicit engine use).
# ---------------------------------------------------------------------------

def search_tiles_engine(op: TensorOp, buf, *,
                        caps: Mapping[str, int] | None = None,
                        prefer_large: bool = True):
    """Memoized + vectorized + pruned §II-B tile search.

    Result-identical to ``core.tiling.search_tiles_reference``; the cache
    key is structural, so the returned schedule's ``op_name`` is patched to
    the caller's op when a differently-named twin produced the entry.
    """
    key = ("sched", op_signature(op), _buf_signature(buf),
           _caps_signature(caps), prefer_large)

    def compute():
        if _lattice_overflow_risk(op):
            from .tiling import search_tiles_reference
            return search_tiles_reference(op, buf, caps=caps,
                                          prefer_large=prefer_large)
        return _search_tiles_vectorized(op, buf, caps, prefer_large)

    s = _memo(key, compute)
    # Fresh dicts per caller: the LRU entry is shared process-wide, and a
    # caller mutating schedule.tile/.grid in place must not poison it.
    return dataclasses.replace(s, op_name=op.name, tile=dict(s.tile),
                               grid=dict(s.grid))


def order_grid_engine(op: TensorOp, tile: Mapping[str, int]):
    """Memoized + vectorized Fig. 2 grid-order search (Pallas granularity).

    Evaluates every parallel-dim permutation's HBM fetch bytes with one
    NumPy reduction instead of per-permutation Python accounting; picks the
    first minimum (== the reference ``itertools.permutations`` scan).
    Temporal dims always stay innermost (PSum-stationary rule).
    """
    key = ("order", op_signature(op), _caps_signature(tile))

    def compute():
        # worst case over all permutations is the refetch-everything bound
        # (num_tiles * sum of footprints); past int64-exact territory the
        # vectorized prod would wrap silently, so use the big-int reference.
        worst = op.num_tiles(tile) * sum(
            v.footprint_bytes(tile) for v in op.inputs)
        if worst >= _INT64_SAFE:
            from .exchange import order_grid_for_sharing_reference
            return order_grid_for_sharing_reference(op, tile)
        return _order_grid_vectorized(op, tile)

    return _memo(key, compute)


def _order_grid_vectorized(op: TensorOp, tile):
    import itertools

    from .exchange import GridOrder

    grid = op.grid_shape(tile)
    par = [d.name for d in op.parallel_dims]
    tmp = [d.name for d in op.temporal_dims]
    perms = [tuple(p) + tuple(tmp) for p in itertools.permutations(par)]
    n_dims = len(op.dims)
    name_idx = {d.name: j for j, d in enumerate(op.dims)}
    gs = np.array([grid[d.name] for d in op.dims], dtype=np.int64)
    P = np.array([[name_idx[n] for n in order] for order in perms],
                 dtype=np.int64)                    # (n_perms, n_dims)
    deps = np.zeros((len(op.inputs), n_dims), dtype=bool)
    fp = np.zeros(len(op.inputs), dtype=np.int64)
    for i, v in enumerate(op.inputs):
        fp[i] = v.footprint_bytes(tile)
        for j, d in enumerate(op.dims):
            deps[i, j] = any(e.depends_on(d.name) for e in v.index_exprs)

    dep_at = deps[:, P]                             # (n_inputs, n_perms, n_dims)
    pos = np.arange(n_dims)
    # innermost (largest) position holding a dep, -1 when the operand is
    # invariant to every dim
    innermost = np.where(dep_at.any(axis=2),
                         n_dims - 1 - np.argmax(dep_at[:, :, ::-1], axis=2),
                         -1)
    refetch = dep_at | (pos[None, None, :] < innermost[:, :, None])
    factors = np.where(refetch, gs[P][None, :, :], 1)
    fetch = (factors.prod(axis=2) * fp[:, None]).sum(axis=0)  # (n_perms,)
    best = int(np.argmin(fetch))                    # first occurrence on ties
    naive = int(fp.sum()) * op.num_tiles(tile)
    return GridOrder(perms[best], naive - int(fetch[best]), int(fetch[best]))


def plan_mesh_exchange_engine(op: TensorOp, tile: Mapping[str, int],
                              mesh_shape: tuple[int, int], *,
                              share_rows: bool = True,
                              share_cols: bool = True,
                              row_span_cap: int | None = None,
                              col_span_cap: int | None = None):
    """Memoized mesh-exchange planner (the candidate space — (row, col)
    axis pairs — is tiny, so the win here is caching across the simulator's
    repeated (arch, workload) sweeps, not vectorization)."""
    key = ("mesh", op_signature(op), _caps_signature(tile), mesh_shape,
           share_rows, share_cols, row_span_cap, col_span_cap)

    def compute():
        from .exchange import plan_mesh_exchange_reference
        return plan_mesh_exchange_reference(
            op, tile, mesh_shape, share_rows=share_rows,
            share_cols=share_cols, row_span_cap=row_span_cap,
            col_span_cap=col_span_cap)

    return _memo(key, compute)

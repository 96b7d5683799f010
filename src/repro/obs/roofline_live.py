"""Live roofline/traffic accountant: observed vs predicted bytes + FLOPs.

The paper's headline numbers (2-22x fewer global-buffer fetches, up to 5x
fewer DRAM fetches) are PREDICTIONS from ``analysis/roofline.py``, the
tile-search engine and ``sim/``.  This module closes the loop at runtime:
it derives OBSERVED bytes-moved and FLOPs from what the live system
actually did — the serving engine's per-tick KV-traffic counters, the
prefix-cache/page-pool stats, and XLA's cost analysis of compiled
programs — and lines them up against the analytic prediction as
``observed vs predicted`` rows with a documented tolerance.  A regression
that silently changes the traffic a subsystem generates (scheduler
chunking, COW explosion, a kernel reading the padded page view) breaks
the tolerance instead of hiding in a wall-time.

Two traffic LEVELS mirror the paper's memory hierarchy:

``gb``    (global buffer) — token-exact bytes the COMPUTE consumed:
          per decode/prefill row, the attended context length x the
          per-token KV byte cost.  Predicted and observed use independent
          derivations (a closed-form sum over the request trace vs the
          engine's per-tick accumulation), so equality is an invariant
          of the scheduler/engine bookkeeping, not a tautology.
``dram``  — page-granular bytes the POOL served: the kernel streams whole
          pages, so observed reads round each context up to its page
          boundary.  observed/predicted(gb) quantifies the paging
          overhead and is bounded by ``1 + page_size / min_context``.

For compiled workloads (conv2d here; the dryrun sweep generally) the
observed side is XLA's ``cost_analysis`` of the compiled executable and
the predicted side is the analytic floor (exact MACs, operand+output
bytes) plus the paper scheduler's global-buffer fetch plan.

jax is imported lazily — the serving-side accounting stays jax-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

LEVELS = ("gb", "dram")

# Documented tolerances (ratio bands, observed / predicted) asserted by
# tests/test_obs.py and reported by ``TrafficRow.row()``:
#   * gb   — the two derivations must agree to float rounding; the band
#            allows scheduler-edge slack (budget-split chunks).
#   * dram — page-granularity overhead: every context rounds up to a page
#            boundary, so observed >= predicted(gb) but bounded by one
#            page per row read.
#   * hlo_flops — XLA counts the same MACs the NDRange does (2 flops per
#            MAC); fusion bookkeeping may add epsilon.
#   * hlo_bytes — XLA's "bytes accessed" counts each operand per use, so
#            a fused conv sits above the touch-once floor but within a
#            small factor of it on a single-op program.
TOLERANCES = {"gb": 1.02, "dram": 1.75, "hlo_flops": 1.25, "hlo_bytes": 4.0}


@dataclasses.dataclass(frozen=True)
class TrafficRow:
    """One observed-vs-predicted comparison."""
    workload: str                  # e.g. "paged_decode", "conv2d"
    level: str                     # "gb" | "dram" | "hlo_flops" | ...
    observed: float
    predicted: float
    unit: str = "bytes"
    tolerance: float = 0.0         # ratio band; 0 -> TOLERANCES[level]
    bound: bool = False            # one-sided: only observed <= pred * tol

    @property
    def ratio(self) -> float:
        return self.observed / self.predicted if self.predicted else \
            float("inf")

    @property
    def tol(self) -> float:
        return self.tolerance or TOLERANCES.get(self.level, 1.5)

    @property
    def within(self) -> bool:
        if self.predicted <= 0:
            return False
        if self.bound:
            return self.ratio <= self.tol
        return 1.0 / self.tol <= self.ratio <= self.tol

    def row(self) -> dict:
        return {"workload": self.workload, "level": self.level,
                "observed": self.observed, "predicted": self.predicted,
                "unit": self.unit, "ratio": round(self.ratio, 4),
                "tolerance": self.tol, "within": self.within}


# ---------------------------------------------------------------------------
# Paged-decode serving traffic
# ---------------------------------------------------------------------------

def predict_paged_decode_traffic(
        prompt_lens: Sequence[int], max_new: int, *, page_size: int,
        page_bytes: int, prefill_chunk: int,
        matched: Sequence[int] | None = None) -> dict[str, float]:
    """Closed-form KV traffic for serving ``prompt_lens`` to completion.

    Mirrors the engine's tick accounting from the OUTSIDE: each prefill
    chunk attends over the context cached so far, each decode tick writes
    the previous token and attends over the grown context, and the final
    sampled token is never written back.  ``matched`` gives per-request
    prefix-cache hits (tokens served for free; default all-cold).

    Assumes chunks are never split by the per-tick token budget (size the
    engine's ``prefill_token_budget`` >= ``prefill_chunk`` x concurrent
    prefills when comparing against this) and greedy decode runs the full
    ``max_new`` (``eos_id = -1``).
    """
    bpt = page_bytes / page_size          # per-token KV bytes (K+V+scales)
    gb_tokens = 0                         # token-exact attended context
    dram_tokens = 0                       # page-granular pool reads
    written = 0
    for j, prompt_len in enumerate(prompt_lens):
        start = matched[j] if matched is not None else 0
        pos = start
        while pos < prompt_len:
            pos = min(prompt_len, pos + prefill_chunk)
            gb_tokens += pos
            dram_tokens += -(-pos // page_size) * page_size
        for i in range(1, max_new):
            ctx = prompt_len + i
            gb_tokens += ctx
            dram_tokens += -(-ctx // page_size) * page_size
        written += (prompt_len - start) + (max_new - 1)
    return {
        "gb_read_bytes": gb_tokens * bpt,
        "dram_read_bytes": dram_tokens * bpt,
        "written_bytes": written * bpt,
        "gb_read_tokens": gb_tokens,
        "dram_read_tokens": dram_tokens,
        "written_tokens": written,
    }


def paged_decode_rows(observed: Mapping[str, float],
                      predicted: Mapping[str, float]) -> list[TrafficRow]:
    """Line the engine's observed traffic (``engine.telemetry()
    ["traffic"]``) up against :func:`predict_paged_decode_traffic`."""
    return [
        TrafficRow("paged_decode", "gb", observed["gb_read_bytes"],
                   predicted["gb_read_bytes"]),
        TrafficRow("paged_decode", "dram", observed["dram_read_bytes"],
                   predicted["dram_read_bytes"]),
        TrafficRow("paged_decode", "gb", observed["written_bytes"],
                   predicted["written_bytes"], unit="bytes_written",
                   tolerance=TOLERANCES["gb"]),
    ]


# ---------------------------------------------------------------------------
# Compiled-workload traffic (XLA cost analysis as the observer)
# ---------------------------------------------------------------------------

def observe_compiled(fn, *args) -> dict[str, float]:
    """Compile ``fn(*args)`` and read XLA's cost analysis: observed FLOPs
    and bytes accessed, plus the memory-analysis peak."""
    import jax  # lazy: keep the module importable jax-free

    from repro.runtime import compat

    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    mem = compat.memory_stats(compiled)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "peak_bytes": float(mem["peak_bytes"])}


def conv2d_rows(N: int, H: int, W: int, CI: int, CO: int, KH: int, KW: int,
                *, dtype_bytes: int = 4) -> list[TrafficRow]:
    """Observed-vs-predicted rows for one NHWC VALID conv2d.

    Observed: XLA cost analysis of the compiled conv (the runtime).
    Predicted: exact MAC count (2 FLOPs/MAC) and the touch-once DRAM
    floor (input + weights + output bytes); the paper scheduler's
    global-buffer fetch plan for the same op is attached as a gauge-style
    extra row so the analytic GB prediction rides along with every
    comparison (``analysis/roofline`` closes over it offline).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import TEU_BUFFER, conv2d_op, order_grid_for_sharing, \
        search_tiles

    OH, OW = H - KH + 1, W - KW + 1
    macs = N * OH * OW * CO * CI * KH * KW
    floor_bytes = dtype_bytes * (N * H * W * CI + KH * KW * CI * CO +
                                 N * OH * OW * CO)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    import jax
    x = jnp.asarray(np.zeros((N, H, W, CI), np.float32))
    w = jnp.asarray(np.zeros((KH, KW, CI, CO), np.float32))
    obs = observe_compiled(conv, x, w)

    # the paper's §II-B prediction for the same op: tile schedule + grid
    # order -> HBM->global-buffer fetch bytes on the TEU arch
    op = conv2d_op(CO, CI, OH, OW, KH, KW, bytes_per_elem=dtype_bytes)
    sched = search_tiles(op, TEU_BUFFER)
    plan = order_grid_for_sharing(op, sched.tile)
    return [
        TrafficRow("conv2d", "hlo_flops", obs["flops"], 2.0 * macs,
                   unit="flops"),
        TrafficRow("conv2d", "hlo_bytes", obs["bytes"], floor_bytes),
        # the scheduler's own GB fetch plan vs the refetch-everything
        # bound: the paper's fetch-reduction claim as a runtime row (the
        # plan must never exceed the naive bound)
        TrafficRow("conv2d", "gb", plan.total_fetch_bytes,
                   plan.total_fetch_bytes + plan.resident_bytes_saved,
                   tolerance=1.0 + 1e-9, bound=True),
    ]


def report(rows: Sequence[TrafficRow], *, registry=None) -> list[dict]:
    """Render rows as dicts and mirror them into a metrics registry
    (``obs.REGISTRY`` by default) as gauges keyed by workload/level."""
    if registry is None:
        from . import metrics
        registry = metrics.REGISTRY
    out = []
    for r in rows:
        registry.gauge("traffic_observed", r.observed,
                       workload=r.workload, level=r.level, unit=r.unit)
        registry.gauge("traffic_predicted", r.predicted,
                       workload=r.workload, level=r.level, unit=r.unit)
        registry.gauge("traffic_ratio", r.ratio,
                       workload=r.workload, level=r.level, unit=r.unit)
        out.append(r.row())
    return out

#!/usr/bin/env python3
"""Where a serving cell's set-up goes: run the set-up alone, with the
program's telemetry on, and split ``setup_s`` by what it did.

    python3 bench/setup_split.py --workload qwen3-4b.chat --seed 7

runs everything that ``bench/run.py`` counts as ``setup_s`` for the cell
(JAX's start, the weights drawn from the seed, the engine, the warm-up of
every step program) and no window.  The last line of standard output is a
JSON object:

* ``setup_s``, ``jax_start_s`` (process start to the chips found),
  ``weights_s`` (the seeded draw and the engine built, until the weights
  are on the device), ``warm_up_s`` (``serving.warm_up``);
* ``jit``: the program's ``jit.trace``, ``jit.lower`` and ``jit.compile``
  spans (``repro.obs``) by phase, a span nested in another counted once,
  with ``total_s`` the time any of them covered; ``cache_hits`` and
  ``cache_misses`` of the persistent compile cache;
* ``rest_s``: set-up less JAX's start, the ``jit`` total and the weights'
  time outside it: step executions of the warm-up and host work;
* ``programs``: the slowest ``jit.compile`` spans, by program.

A program whose telemetry has no such spans reports zeros under ``jit``.
Without a TPU, or with fewer chips than the cell asks for, it exits 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import program_trace  # noqa: E402
import serving  # noqa: E402


@contextlib.contextmanager
def _marks(clock=time.monotonic):
    """Times when ``serving.setup`` asks for the weights' key (just before
    the draw) and enters the warm-up (the weights then on the device)."""
    marks = {}
    jax_key, warm_up = harness.jax_key, serving.warm_up

    def key(seed):
        marks.setdefault("weights", clock())
        return jax_key(seed)

    def warm(engine, *a, **kw):
        import jax
        jax.block_until_ready(engine.params)
        marks["warm_up"] = clock()
        return warm_up(engine, *a, **kw)

    harness.jax_key, serving.warm_up = key, warm
    try:
        yield marks
    finally:
        harness.jax_key, serving.warm_up = jax_key, warm_up


def measure(cell, seed: int, t_start: float, log) -> dict:
    """Run the cell's set-up with telemetry on; returns the split."""
    t_jax = time.monotonic()
    with _marks() as marks:
        obs = serving.setup(cell, seed, True, log)[-1]
    t_end = time.monotonic()
    events = obs.tracer.spans()
    counters = obs.snapshot()["counters"]
    jit = program_trace.jit_phases(events)
    weights_jit = program_trace.jit_phases(
        events, marks["weights"], marks["warm_up"])["total_s"]
    weights = marks["warm_up"] - marks["weights"]
    compiles = sorted((e for e in events if e["name"] == "jit.compile"),
                      key=lambda e: -e["dur"])
    setup_s = t_end - t_start
    return {"setup_s": setup_s, "jax_start_s": t_jax - t_start,
            "weights_s": weights, "warm_up_s": t_end - marks["warm_up"],
            "jit": jit,
            "cache_hits": counters.get("jit.cache_hits", 0),
            "cache_misses": counters.get("jit.cache_misses", 0),
            "rest_s": setup_s - (t_jax - t_start) - jit["total_s"]
            - (weights - weights_jit),
            "programs": [[e["args"].get("fun", ""), e["dur"]]
                         for e in compiles[:5]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)

    cell = harness.Cell(a.workload)
    if cell.traffic["kind"] != "serve":
        print(f"setup_split: {a.workload} is not a serving cell",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        harness.accelerator_devices(cell.chips)
    except harness.NoAccelerator as e:
        print(f"setup_split: {e}; this runs on the chip only",
              file=sys.stderr)
        return 2
    split = measure(cell, a.seed, T_START,
                    lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(split), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload qwen3-4b.chat --seed 7 --seconds 51 \
        --trace 0

``--trace 0`` reports the cell's end-to-end metrics with the program's
telemetry off.  ``--trace 1`` turns the program's telemetry on, wraps the
harness's calls in profiler annotations, traces a few seconds of the
window and reports the per-layer metrics read from that trace, its spans
and counters, with the device's busy time and a breakdown.

Set-up (weights drawn on the device from the seed, every step program
compiled or loaded from the persistent cache, warm-up) counts as
``setup_s``; nothing compiles inside the window, and the count of
programs that did is printed.  After the window the served tokens are
compared with the configuration's float32 reference; the numbers compared
are printed with their limits as the last lines on standard error and
under ``checks``, the last key of the result.  The last line of standard
output is the result.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

DRIVERS = {"serve": "serving", "train": "training"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float = T_START) -> tuple[dict, bool]:
    """Everything after the device check: returns (result dict, correct)."""
    import importlib
    driver = importlib.import_module(DRIVERS[cell.traffic["kind"]])
    metrics, attempted, failed, checks, dev, layer_ctx = driver.run(
        cell, seed, seconds, trace, t_start, devices, log)
    breakdown = None
    if trace:
        import trace_reduce
        red = trace_reduce.reduce(layer_ctx["xplane"])
        layer_ctx["reduction"] = red
        layer_ctx["peaks"] = harness.peaks_for(dev["kind"], cell.root)
        metrics = {}
        for m in cell.per_layer:
            v = harness.layer_reader(m["name"], cell.root).read(layer_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        log(f"[trace] busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s;"
            f" top ops {red['device_ops'][:5]}")
    correct = driver.checks_pass(checks)
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev, "checks": checks,
            "breakdown": breakdown}, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    cell = harness.Cell(a.workload)
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = harness.accelerator_devices(cell.chips)
    except harness.NoAccelerator as e:
        log(f"bench: {e}; this benchmark runs on the chip only")
        return 2
    log(f"[chip] {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache}")
    res, _ = execute(cell, a.seed, a.seconds, bool(a.trace), devices)
    print(harness.result_line(**res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

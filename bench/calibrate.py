#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limits, made on the
chip in one process (benchmark runs never call this).

    # offered-load sweep: latency tails and throughput at each rate
    python bench/calibrate.py sweep --workload qwen3-4b.chat \\
        --rates 2,3,4,5 --seconds 30

    # the program's widest served-token logit gap on each seed, and on
    # the first --control seeds the fp8 control's gap on the same tokens
    python bench/calibrate.py correct --workload qwen3-4b.chat \\
        --seeds 101,102,103 --control 3 --seconds 15

    # a training cell: the program's gaps on each seed; the bfloat16
    # control's on the first --control seeds; the program with half of
    # each batch left out on the first --faults seeds
    python bench/calibrate.py train --workload mamba2-370m.train \
        --seeds 101,102,103 --control 3 --faults 3

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import serving  # noqa: E402
import training  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def sweep(cell, rates, seconds):
    _, _, _, engine, obs = serving.setup(cell, 0, False, log)
    for rate in rates:
        traffic = dict(cell.traffic, rate_per_s=rate)
        w = serving.window(cell, engine, 0, seconds, False, obs, log, traffic)
        print(json.dumps({
            "rate_per_s": rate, "attempted": w["attempted"],
            "completed": len(w["completed"]), "in_flight": w["in_flight"],
            "ran_s": w["t_end"], "tokens_per_s": w["tokens_per_s"],
            "ttft_ms": {q: harness.percentile(w["ttft_ms"], q)
                        for q in (50, 90, 95)},
            "tpot_ms": {q: harness.percentile(w["tpot_ms"], q)
                        for q in (50, 90, 95)},
            "compiles": w["compiles"]}), flush=True)
        engine.reset_serving_state()


def correct(cell, seeds, n_control, seconds):
    ref = cell.reference()
    adapter = None
    for i, seed in enumerate(seeds):
        _, adapter, params, engine, obs = serving.setup(
            cell, seed, False, log, adapter)
        w = serving.window(cell, engine, seed, seconds, False, obs, log)
        sample = serving._sample(w["completed"], cell.traffic, seed)
        compiles = w["compiles"]
        del engine, w
        gc.collect()
        reqs = [(r.prompt, r.served) for r in sample]
        t = time.monotonic()
        prog = max(float(g.max()) for g in
                   ref.served_gaps(cell.config, params, reqs))
        row = {"seed": seed, "program_gap": prog,
               "served_tokens": int(sum(len(r.served) for r in sample)),
               "requests": len(sample), "compiles": compiles,
               "reference_s": time.monotonic() - t}
        if i < n_control:
            row["control_gap"] = max(float(g.max()) for g in ref.served_gaps(
                cell.config, params, reqs, quant="fp8"))
        print(json.dumps(row), flush=True)
        del params
        gc.collect()


def _half_batch(make):
    """make_train_step whose step sees the first half of each batch."""
    import jax

    def make_half(forward, hyper):
        step = make(forward, hyper)

        def half(params, opt_state, batch, grad_scale=None):
            return step(params, opt_state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return half
    return make_half


def train(cell, seeds, n_control, n_faults):
    import jax.numpy as jnp
    from repro import training as program
    make = program.make_train_step
    for i, seed in enumerate(seeds):
        rows = {}
        kinds = ["program"] + (["half_batch"] if i < n_faults else [])
        for kind in kinds:
            program.make_train_step = (_half_batch(make)
                                       if kind == "half_batch" else make)
            t = time.monotonic()
            fam, bundle, step, params, opt, batch = training.build(cell, seed)
            params, opt, prog = training.first_steps(
                cell, fam, bundle, step, params, opt, batch, seed)
            rows[kind] = (prog, time.monotonic() - t)
            del params, opt
            gc.collect()
        program.make_train_step = make
        t = time.monotonic()
        ref = training.reference_readings(cell, seed)
        out = {"seed": seed, "reference_s": time.monotonic() - t,
               "program_s": rows["program"][1],
               "losses": {"program": rows["program"][0]["losses"],
                          "reference": ref["losses"]}}
        for kind, (readings, _) in rows.items():
            out[kind] = training.gaps(readings, ref)
        if i < n_control:
            low = training.reference_readings(cell, seed, dtype=jnp.bfloat16)
            out["control"] = training.gaps(low, ref)
        if i == 0:
            out["leaves"] = {k: [rows["program"][0]["grad_norms"][k],
                                 ref["grad_norms"][k],
                                 rows["program"][0]["change_norms"][k],
                                 ref["change_norms"][k]]
                             for k in ref["grad_norms"]}
        print(json.dumps(out), flush=True)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "correct", "train"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        harness.accelerator_devices(cell.chips)
    except harness.NoAccelerator as e:
        log(f"calibrate: {e}")
        return 2
    if a.mode == "sweep":
        sweep(cell, [float(r) for r in a.rates.split(",")], a.seconds)
    elif a.mode == "train":
        train(cell, [int(s) for s in a.seeds.split(",")], a.control,
              a.faults)
    else:
        correct(cell, [int(s) for s in a.seeds.split(",")], a.control,
                a.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())

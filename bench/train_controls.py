#!/usr/bin/env python3
"""The faults a training cell's check has to catch, run on the cell's
chips: each control's readings are compared with the float32 reference's
as the cell's check compares a sound run's, and each has to read above
one of the cell's limits.

    python3 bench/train_controls.py --workload qwen3-4b-16l.ring-train \
        --seed 7

* ``half_sequence``: the loss taken over the second half of each
  sequence's tokens only (the forward still sees them all);
* ``skip_last_hop``: every ring pass stops one hop short, so the last
  chip's queries never see the first shard's keys;
* ``bf16``: the reference itself in bfloat16, in the program's place.

The last line of standard output is a JSON object: each control's gaps
beside the limits.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

RING = "repro.parallel.ring_attention"
FAULTS = ("half_sequence", "skip_last_hop", "bf16")


def half_sequence(make):
    def make_broken(forward, hyper):
        step = make(forward, hyper)

        def broken(params, opt_state, batch, grad_scale=None):
            S = batch["labels"].shape[1]
            return step(params, opt_state,
                        {"tokens": batch["tokens"],
                         "labels": batch["labels"][:, S // 2:]})
        return broken
    return make_broken


def skip_last_hop(make):
    def make_broken(forward, hyper):
        import jax
        step = make(forward, hyper)
        loop = jax.lax.fori_loop

        def short(lo, hi, body, init, **kw):
            if body.__module__ == RING:
                hi = hi - 1
            return loop(lo, hi, body, init, **kw)

        def broken(params, opt_state, batch, grad_scale=None):
            jax.lax.fori_loop = short
            try:
                return step(params, opt_state, batch)
            finally:
                jax.lax.fori_loop = loop
        return broken
    return make_broken


def program_readings(cell, seed: int, fault=None) -> dict:
    """The first steps' readings of the program's step, or of the step
    broken by ``fault``; traced afresh (no cached trace of the ring)."""
    import jax

    from repro import training as program
    import training
    make = program.make_train_step
    if fault is not None:
        program.make_train_step = fault(make)
    jax.clear_caches()
    try:
        fam, bundle, step, params, opt, batch = training.build(cell, seed)
        _, _, prog = training.first_steps(cell, fam, bundle, step, params,
                                          opt, batch, seed)
    finally:
        program.make_train_step = make
        jax.clear_caches()
    return prog


def run(cell, seed: int, log=lambda m: None) -> dict:
    """The gaps (``training.gaps``) of the sound program and of each
    control against the reference."""
    import gc

    import jax.numpy as jnp

    import training
    ref = training.reference_readings(cell, seed)
    out = {}
    for name in ("sound",) + FAULTS:
        if name == "sound":
            low = program_readings(cell, seed)
        elif name == "bf16":
            low = training.reference_readings(cell, seed, dtype=jnp.bfloat16)
        else:
            low = program_readings(cell, seed, globals()[name])
        out[name] = training.gaps(low, ref)
        gc.collect()
        log(f"[control] {name}: {out[name]}")
    return out


def caught(gaps: dict, limits: dict) -> bool:
    """Whether the cell's check (``training.checks_pass``, which decides
    ``correct``) fails on these gaps."""
    import training
    return not training.checks_pass(
        {k: {"value": gaps[k], "limit": limits[k]} for k in training.CHECKS})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    try:
        harness.accelerator_devices(cell.chips)
    except harness.NoAccelerator as e:
        print(f"train_controls: {e}; the controls run on the chip only",
              file=sys.stderr)
        return 2
    gaps = run(cell, a.seed, lambda m: print(m, file=sys.stderr, flush=True))
    limits = cell.traffic["check"]["limits"]
    print(json.dumps({"controls": gaps, "limits": limits,
                      "caught": {k: caught(g, limits)
                                 for k, g in gaps.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Prove that the serving and training paths run on a TPU, at full width.

    python chip_smoke.py               # one chip: serve + train
    python chip_smoke.py --four-chips  # 2x2 host: ring attention

One process drives the chip; it starts no child that touches JAX.  Each
phase goes through the entry points a user calls
(``repro.launch.serve.build_engine`` and ``repro.launch.train.run``) with
random weights drawn from a fixed seed, and checks what comes out:

* serve: qwen3-4b at its published widths (36 layers, d=2560, GQA 32/8,
  vocab 151936, bf16) on the paged KV path serves 8 requests of 256
  prompt tokens (half share a 128-token prefix) and 32 new tokens each.
  Every outcome must be ``ok``, the paged decode must have dispatched to
  the Pallas kernel, and that kernel must agree with the XLA gather path
  on the served pool.
* train: mamba2-370m at its published widths takes 3 steps at batch 4 x
  2048 tokens (sized from the compiled step's memory analysis: about
  9 GB of the chip's 16); every loss must be finite.
* ``--four-chips`` (only this phase): a qwen3-4b train step at published
  widths, depth cut to 16 layers, S=4096 on a (1, 4) host mesh, where
  the ring policy picks the ppermute ring; the same step with
  ``REPRO_RING_ATTN=replicated`` must give the same loss and gradient
  norm.

Earlier lines report compile seconds, error bounds, tokens served and
peak device memory.  The last line is one JSON object with the device as
JAX reports it.  Without a TPU the script exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_ARCH, TRAIN_ARCH = "qwen3-4b", "mamba2-370m"
PROMPT, SHARED, NEW, REQUESTS, SLOTS = 256, 128, 32, 8, 4
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 2048
RING_LAYERS, RING_SEQ = 16, 4096
# Pallas vs XLA paged decode on bf16 inputs: both accumulate in f32 and
# round the output to bf16, whose unit in the last place is at most 2^-7
# of the element; allow two such units of each row's largest element.
# Half the rows are short (at most two pages), where one key masked
# wrongly or one wrong page moves the output by a few percent of its
# scale; in the long rows such an error is diluted below the bound.
DECODE_REL_TOL = 2.0 ** -6
DECODE_ROWS = 8
# ring vs replicated attention, same mesh and weights: bf16 activations
# and different f32 summation orders
LOSS_ABS_TOL, GNORM_REL_TOL = 1e-2, 2e-2


class _CompileClock:
    """Sums XLA backend compile time, per phase, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


def _peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def serve_phase(clock) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.models.layers import paged_decode_attention
    from repro.obs import REGISTRY

    t0 = time.time()
    engine, vocab = build_engine(
        SERVE_ARCH, smoke=False, kv_mode="paged", slots=SLOTS,
        max_len=PROMPT + NEW, max_new=NEW, prefill_chunk=PROMPT,
        prefill_token_budget=SLOTS * PROMPT, seed=0)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, vocab, SHARED)
    rids = []
    for i in range(REQUESTS):
        prompt = rng.integers(0, vocab, PROMPT).astype(np.int32)
        if i % 2 == 0:
            prompt[:SHARED] = shared
        rids.append(engine.submit(prompt))
    results = engine.run()
    wall = time.time() - t0
    outcomes = [engine.outcomes.get(r) for r in rids]
    assert outcomes == ["ok"] * REQUESTS, outcomes
    served = [len(results[r]) for r in rids]
    assert served == [NEW] * REQUESTS, served
    pallas = REGISTRY.get_counter("kernel_dispatch",
                                  kernel="paged_flash_decode", impl="pallas")
    assert pallas > 0, "paged decode did not dispatch to the Pallas kernel"
    prefix = engine.prefix_stats()
    print(f"[serve] {SERVE_ARCH} full width: {REQUESTS} requests ok, "
          f"{sum(served)} tokens, prefix hits {prefix['hits']}/"
          f"{prefix['lookups']}, wall {wall:.1f}s "
          f"(compile {clock.lap():.1f}s)", flush=True)

    # the kernel against the XLA gather path on the pool just served,
    # reading only pages that hold written K/V
    cfg = engine.bundle.cfg
    k0, v0 = engine.pool["k"][0], engine.pool["v"][0]
    page = k0.shape[1]
    written = np.flatnonzero(np.asarray(jnp.any(k0 != 0, axis=(1, 2, 3))))
    written = written[written > 0]           # page 0 is the trash page
    assert written.size, "the served pool holds no written page"
    mp = engine.kv.cfg.pages_per_slot
    table = rng.choice(written, (DECODE_ROWS, mp)).astype(np.int32)
    half = DECODE_ROWS // 2
    lengths = np.concatenate([                # cached tokens before the step
        rng.integers(0, 2 * page, half),
        rng.integers(2 * page, mp * page, DECODE_ROWS - half)]).astype(np.int32)
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (DECODE_ROWS, 1, cfg.n_heads, cfg.dh), jnp.bfloat16)

    def decode(impl):
        fn = jax.jit(lambda *a: paged_decode_attention(*a, impl=impl))
        out = fn(q, k0, v0, table, lengths)
        return np.asarray(out, np.float32).reshape(DECODE_ROWS, -1)

    got, want = decode("pallas"), decode("xla")
    err = np.max(np.abs(got - want), axis=1)
    bound = DECODE_REL_TOL * np.max(np.abs(want), axis=1)
    worst = int(np.argmax(err / np.maximum(bound, 1e-30)))
    print(f"[serve] paged decode pallas vs xla, {DECODE_ROWS} rows of "
          f"{lengths.min() + 1}..{lengths.max() + 1} keys over "
          f"{written.size} written pages: max abs err per row "
          f"{np.array2string(err, precision=3)}, bound per row "
          f"{np.array2string(bound, precision=3)} (worst row {worst}: "
          f"{err[worst]:.3e} <= {bound[worst]:.3e})", flush=True)
    assert np.isfinite(got).all() and (err <= bound).all(), (err, bound)
    del engine
    return {"tokens": sum(served), "decode_err": float(err.max())}


def train_phase(clock) -> dict:
    from repro.launch.train import run

    out = run(TRAIN_ARCH, smoke=False, steps=TRAIN_STEPS,
              seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH)
    losses = out["losses"]
    assert len(losses) == TRAIN_STEPS, losses
    assert all(math.isfinite(x) for x in losses), losses
    print(f"[train] {TRAIN_ARCH} full width, batch {TRAIN_BATCH} x "
          f"{TRAIN_SEQ}: losses {losses} (compile {clock.lap():.1f}s)",
          flush=True)
    return {"losses": losses}


def four_chip_phase(clock) -> dict:
    from repro.configs import base as cbase
    from repro.launch.train import run
    from repro.obs import REGISTRY

    def fused_rings():
        return REGISTRY.get_counter("kernel_dispatch",
                                    kernel="ring_attention", impl="pallas")

    mode = cbase.decide_ring(cbase.ring_attn_policy(), seq_len=RING_SEQ,
                             ring_size=4)
    assert mode == "ring", mode

    def step(ring_mode):
        os.environ["REPRO_RING_ATTN"] = ring_mode
        try:
            out = run(SERVE_ARCH, smoke=False, steps=1, seq_len=RING_SEQ,
                      global_batch=1, mesh_kind="host",
                      n_layers=RING_LAYERS)
        finally:
            os.environ.pop("REPRO_RING_ATTN", None)
        loss, gnorm = out["losses"][0], out["grad_norms"][0]
        print(f"[four-chips] {SERVE_ARCH} {RING_LAYERS} layers, S="
              f"{RING_SEQ}, attention {ring_mode}: loss {loss!r} grad norm "
              f"{gnorm!r} (compile {clock.lap():.1f}s)", flush=True)
        return loss, gnorm

    ring = step("ring")
    fused = fused_rings()
    # the ring's per-hop fold must be the compiled Pallas flash kernel
    print(f"[four-chips] ring_attention dispatches with the fused Pallas "
          f"fold: {fused}", flush=True)
    assert fused > 0, "the ring did not fold its hops with the Pallas kernel"
    replicated = step("replicated")
    assert fused_rings() == fused, "the replicated step took the ring"
    dloss = abs(ring[0] - replicated[0])
    dnorm = abs(ring[1] - replicated[1]) / max(abs(replicated[1]), 1e-30)
    print(f"[four-chips] ring vs replicated: |d loss| {dloss:.3e} "
          f"(bound {LOSS_ABS_TOL}), rel d grad norm {dnorm:.3e} "
          f"(bound {GNORM_REL_TOL})", flush=True)
    assert all(math.isfinite(x) for x in ring + replicated)
    assert dloss <= LOSS_ABS_TOL and dnorm <= GNORM_REL_TOL, (dloss, dnorm)
    return {"loss": ring[0], "grad_norm": ring[1]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ring-attention phase")
    a = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              "runs on the chip only", file=sys.stderr)
        return 2
    want = 4 if a.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"[chip] {dev.device_kind} x{len(devices)}, compile cache "
          f"{cache}", flush=True)

    clock = _CompileClock()
    if a.four_chips:
        four_chip_phase(clock)
    else:
        serve_phase(clock)
        # the served model's 8.8 GB must be gone before the trainer's 9 GB
        # arrive; reference cycles inside the engine wait for a collection
        gc.collect()
        in_use = (dev.memory_stats() or {}).get("bytes_in_use", 0)
        print(f"[chip] bytes in use after serving: {in_use}", flush=True)
        train_phase(clock)
    print(f"[chip] peak bytes in use on device 0: {_peak_bytes(dev)}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

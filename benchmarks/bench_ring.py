"""Ring-attention fwd/bwd benchmark: the §Perf B6 acceptance table.

Three context-parallel schedules over the same (q, k, v):

  * ``allgather``  — the replicated-k/v shard_map (§Perf B5): every
    device holds the full k/v, the chip-scale "gather the operand into
    every tile" baseline the paper criticizes;
  * ``ring_naive`` — the ppermute ring with its fold loop reverse-
    differentiated by JAX: the backward that stacked one (S/m x S/m) f32
    score tile per hop and kept the ring opt-in (ROADMAP §Perf B6,
    "refuted as measured");
  * ``ring_vjp``   — the memory-flat custom VJP
    (``parallel.ring_attention``): backward recomputes each hop's tile
    and circulates dk/dv accumulators with the shards.

Per schedule: fwd and bwd (value_and_grad) wall time, the per-device HBM
traffic of the bwd program (``analysis.hlo_cost.module_cost`` — the
roofline "memory term", also printed as milliseconds at HBM_BW), and the
XLA temp arena (``compat.memory_stats``), where the naive path's stacked
residuals live.

Acceptance: ``ring_vjp`` bwd must beat ``ring_naive`` bwd on BOTH time
and memory term, and sit within noise of ``allgather`` bwd time at lower
per-device traffic bytes.  The ``ring_bwd_vjp_vs_naive`` summary row
carries the ratios.

The ring needs a mesh, so the table is produced by an 8-virtual-device
subprocess (same pattern as tests/test_distributed.py); run directly:
``PYTHONPATH=src python benchmarks/bench_ring.py`` (``--smoke`` for CI).
"""
import argparse
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (B, S, H, Hkv, Dh); mesh is (2 data, 4 model) -> S/m = S/4 per device
FULL = (2, 2048, 8, 4, 64)
SMOKE = (2, 512, 8, 4, 64)


def _worker(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.analysis.hlo_cost import module_cost
    from repro.analysis.roofline import HBM_BW
    from repro.models import layers
    from repro.parallel.ring_attention import ring_attention
    from repro.runtime import compat

    B, S, H, Hkv, Dh = SMOKE if smoke else FULL
    reps = 1 if smoke else 2
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh),
                          jnp.float32)

    paths = {
        "allgather": lambda q, k, v: layers._attention_ring(
            q, k, v, causal=True, window=None, ring="replicated"),
        "ring_naive": lambda q, k, v: ring_attention(
            q, k, v, causal=True, window=None, impl="naive"),
        "ring_vjp": lambda q, k, v: ring_attention(
            q, k, v, causal=True, window=None, impl="vjp"),
    }

    def timed(fn, *args):
        out = fn(*args)           # compile + warm
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    stats = {}
    for name, f in paths.items():
        def loss(q, k, v, f=f):
            return (f(q, k, v).astype(jnp.float32) ** 2).sum()

        with compat.set_mesh(mesh):
            fwd = jax.jit(f)
            bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            t_fwd = timed(fwd, q, k, v)
            t_bwd = timed(bwd, q, k, v)
            compiled = bwd.lower(q, k, v).compile()
        cost = module_cost(compiled.as_text())   # per-device (SPMD shapes)
        mem = compat.memory_stats(compiled)
        stats[name] = dict(t_fwd=t_fwd, t_bwd=t_bwd, hbm=cost.bytes,
                           temp=mem["temp_bytes"])
        print(f"ring_fwd_{name},{t_fwd * 1e6:.0f},S={S};mesh=2x4")
        print(f"ring_bwd_{name},{t_bwd * 1e6:.0f},"
              f"hbm_mb_dev={cost.bytes / 1e6:.1f};"
              f"mem_term_ms={cost.bytes / HBM_BW * 1e3:.2f};"
              f"temp_mb={mem['temp_bytes'] / 1e6:.1f}")

    nv, vj, ag = stats["ring_naive"], stats["ring_vjp"], stats["allgather"]
    print(f"ring_bwd_vjp_vs_naive,0,"
          f"speedup={nv['t_bwd'] / vj['t_bwd']:.2f}x;"
          f"hbm_ratio={vj['hbm'] / nv['hbm']:.2f};"
          f"temp_ratio={vj['temp'] / max(1, nv['temp']):.2f}")
    print(f"ring_bwd_vjp_vs_allgather,0,"
          f"time_ratio={vj['t_bwd'] / ag['t_bwd']:.2f};"
          f"hbm_ratio={vj['hbm'] / ag['hbm']:.2f};"
          f"temp_ratio={vj['temp'] / max(1, ag['temp']):.2f}")


def main(csv=True, smoke: bool = False):
    """Spawn the 8-device worker and relay its CSV rows."""
    env = dict(os.environ)
    # virtual CPU devices; CPU-only so a parent holding the chip (e.g.
    # benchmarks/run.py on a TPU host) never shares it with this child
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    if smoke:
        cmd.append("--smoke")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=1800)
    if p.returncode != 0:
        raise RuntimeError(f"bench_ring worker failed:\n{p.stdout}\n"
                           f"{p.stderr}")
    rows = []
    for line in p.stdout.splitlines():
        if line.startswith("ring_"):
            rows.append(line)
            print(line)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes (short sequence, single rep)")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run measurements in THIS process "
                         "(expects the 8-device XLA_FLAGS already set)")
    a = ap.parse_args()
    if a.worker:
        _worker(a.smoke)
    else:
        main(csv=True, smoke=a.smoke)

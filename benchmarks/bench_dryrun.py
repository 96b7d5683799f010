"""Model-zoo benchmark: dry-run roofline cells + a live end-to-end table.

Two sections, both CSV (``name,us_per_call,derived``):

``dryrun_<arch>_<shape>``
    The three-term roofline rows derived from the 512-virtual-device
    dry-run cells under ``results/dryrun`` (produced by
    ``python -m repro.launch.dryrun``; rows appear only for cells that
    exist — the sweep is too heavy to run inside the benchmark).

``e2e_<arch>``
    Live end-to-end train-step timing for the model zoo: every arch's
    smoke bundle runs REAL steps on an (2 data x 4 model) 8-virtual-
    device host mesh — params sharded by ``parallel.sharding.param_specs``
    exactly like the launcher — and reports wall time per step, tokens/s,
    and the per-device compiled-memory peak (``compat.memory_stats``).
    This is the ROADMAP "benchmark the model zoo end-to-end" table; the
    device count must be fixed before jax initializes, so the rows come
    from a worker subprocess.  ``--smoke`` shrinks to three
    representative archs (dense / MoE / SSM) and a shorter sequence for
    CI.

Run directly: ``PYTHONPATH=src python benchmarks/bench_dryrun.py``
(``--smoke`` for the CI-sized table, ``--no-e2e`` for cells only).
"""
import argparse
import glob
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E2E_SMOKE_ARCHS = ("qwen3-4b", "olmoe-1b-7b", "mamba2-370m")
E2E_MESH = (2, 4)                     # (data, model) on 8 host devices


# ---------------------------------------------------------------------------
# section 1: cached dry-run cells -> roofline rows
# ---------------------------------------------------------------------------

def rows(mesh="single"):
    from repro.launch.dryrun import RESULTS_DIR, roofline_from_cell
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, f"*__{mesh}.json"))):
        with open(path) as f:
            cell = json.load(f)
        if cell.get("status") == "skipped":
            out.append({"arch": cell["arch"], "shape": cell["shape"],
                        "status": "skipped", "reason": cell["reason"]})
            continue
        rep = roofline_from_cell(cell)
        if rep is None:
            out.append({"arch": cell["arch"], "shape": cell["shape"],
                        "status": cell.get("status", "?")})
            continue
        out.append({"status": "ok", **rep.row()})
    return out


# ---------------------------------------------------------------------------
# section 2: live end-to-end steps (worker subprocess, 8 host devices)
# ---------------------------------------------------------------------------

def _e2e_worker(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCH_IDS, get_bundle
    from repro.optim import adamw_init
    from repro.parallel.sharding import param_specs
    from repro.runtime import compat
    from repro.training import TrainHyper, make_train_step

    archs = E2E_SMOKE_ARCHS if smoke else ARCH_IDS
    B, S = (4, 64) if smoke else (4, 256)
    steps = 2 if smoke else 3
    mesh = compat.make_mesh(E2E_MESH, ("data", "model"))
    key = jax.random.PRNGKey(0)

    for arch in archs:
        bundle = get_bundle(arch, smoke=True)
        cfg = bundle.cfg
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": tokens}
        if bundle.kind == "vlm":
            Pv = cfg.vision_tokens
            batch["tokens"] = batch["tokens"][:, :S - Pv]
            batch["labels"] = batch["labels"][:, :S - Pv]
            batch["vision"] = jnp.zeros((B, Pv, cfg.d_model), cfg.dtype)
        if bundle.kind == "audio":
            batch["frames"] = jnp.zeros((B, cfg.n_audio_ctx, cfg.d_model),
                                        cfg.dtype)
        params = bundle.init_params(jax.random.fold_in(key, 1))
        pspecs = param_specs(bundle.kind, params, mesh)
        psh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        step = make_train_step(bundle.forward, TrainHyper())
        rep = NamedSharding(mesh, P())
        opt_sh = {"mu": psh, "nu": psh, "step": rep}
        with compat.set_mesh(mesh):
            params = jax.device_put(params, psh)
            opt = jax.device_put(adamw_init(params), opt_sh)
            # pin out_shardings to the input layouts so the compiled step
            # is a fixed point: (params, opt) feed straight back into the
            # AOT executable (jit dispatch would compile a second time)
            jitted = jax.jit(step, out_shardings=(psh, opt_sh, rep))
            t0 = time.perf_counter()
            compiled = jitted.lower(params, opt, batch).compile()
            compile_s = time.perf_counter() - t0
            mem = compat.memory_stats(compiled)
            # every step runs the AOT executable (jit dispatch would
            # re-trace and compile a second time); warm once for buffer
            # setup, then time real steps
            params, opt, m = compiled(params, opt, batch)
            jax.block_until_ready(m["loss"])
            best = float("inf")
            for _ in range(steps):
                t0 = time.perf_counter()
                params, opt, m = compiled(params, opt, batch)
                jax.block_until_ready(m["loss"])
                best = min(best, time.perf_counter() - t0)
        toks = batch["tokens"].shape[0] * batch["tokens"].shape[1]
        print(f"e2e_{arch},{best * 1e6:.0f},"
              f"step_ms={best * 1e3:.1f};tok_s={toks / best:.0f};"
              f"peak_mb_dev={mem['peak_bytes'] / 1e6:.1f};"
              f"compile_s={compile_s:.1f};loss={float(m['loss']):.3f}")


def e2e_rows(smoke: bool = False) -> list:
    env = dict(os.environ)
    # virtual CPU devices; CPU-only so a parent holding the chip (e.g.
    # benchmarks/run.py on a TPU host) never shares it with this child
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(__file__), "--e2e-worker"]
    if smoke:
        cmd.append("--smoke")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3600)
    if p.returncode != 0:
        raise RuntimeError(f"e2e worker failed:\n{p.stdout}\n{p.stderr}")
    return [ln for ln in p.stdout.splitlines() if ln.startswith("e2e_")]


def main(csv=True, smoke: bool = False, e2e: bool = True):
    rs = rows()
    if csv:
        for r in rs:
            tag = f"dryrun_{r['arch']}_{r['shape']}"
            if r["status"] != "ok":
                print(f"{tag},0,{r['status']}")
                continue
            dom = r["dominant"]
            t = max(r["t_compute_ms"], r["t_memory_ms"],
                    r["t_collective_ms"])
            print(f"{tag},{t*1e3:.0f},"
                  f"dom={dom} rf={r['roofline_frac']:.2f} "
                  f"useful={r['useful_ratio']:.2f} "
                  f"hbm={r['hbm_gb_per_device']:.1f}GB")
    if e2e:
        for line in e2e_rows(smoke=smoke):
            print(line)
    return rs


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized e2e table (3 archs, short sequence)")
    ap.add_argument("--no-e2e", action="store_true",
                    help="cached dry-run cells only")
    ap.add_argument("--e2e-worker", action="store_true",
                    help="internal: run the e2e measurements in THIS "
                         "process (expects 8-device XLA_FLAGS set)")
    a = ap.parse_args()
    if a.e2e_worker:
        _e2e_worker(a.smoke)
    else:
        main(csv=True, smoke=a.smoke, e2e=not a.no_e2e)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell: jax.jit(step, in_shardings).lower(*ShapeDtypeStructs)
.compile(), then record memory_analysis (bytes/device — proves it fits),
cost_analysis (FLOPs/bytes for §Roofline) and the collective-bytes parse of
the optimized HLO. Results stream into results/dryrun/<cell>.json so an
interrupted sweep resumes where it stopped.

Usage:
    python -m repro.launch.dryrun [--arch ID] [--shape NAME]
        [--mesh single|multi|both] [--force]
"""
import argparse
import json
import os
import time
import traceback

import jax

from repro.analysis.hlo_cost import module_cost
from repro.analysis.roofline import (RooflineReport, collective_bytes,
                                     model_flops_decode, model_flops_train)
from repro.configs import ARCH_IDS, SHAPES, get_bundle
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import step_in_shardings
from repro.runtime import compat

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _cell_path(arch, shape, mesh_name, ring=None):
    safe = arch.replace(".", "_")
    # ring-pinned cells cache separately (and out of bench_dryrun's
    # `*__<mesh>.json` glob) so mode comparisons never read stale cells
    # traced under a different attention mode.
    suffix = f"__ring-{ring}" if ring else ""
    return os.path.join(RESULTS_DIR,
                        f"{safe}__{shape}__{mesh_name}{suffix}.json")


def run_cell(arch: str, shape: str, mesh_name: str, *, force: bool = False,
             ring: str | None = None) -> dict:
    """Lower + compile one cell.  ``ring`` pins the context-parallel
    attention mode for this cell ('ring' | 'replicated' | 'off' | 'auto')
    via the REPRO_RING_ATTN policy env read at trace time; None keeps the
    ambient policy."""
    path = _cell_path(arch, shape, mesh_name, ring)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    from repro.obs import get_telemetry
    tel = get_telemetry()
    bundle = get_bundle(arch)
    t0 = time.time()
    result = {"arch": arch, "shape": shape, "mesh": mesh_name}
    if ring:
        result["ring"] = ring
    ok, why = bundle.supports(shape)
    if not ok:
        result.update(status="skipped", reason=why)
    else:
        prev_ring = os.environ.get("REPRO_RING_ATTN")
        try:
            if ring:
                os.environ["REPRO_RING_ATTN"] = ring
            mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
            chips = mesh.devices.size
            args, shardings, step, donate = step_in_shardings(
                bundle, shape, mesh)
            with compat.set_mesh(mesh), \
                    tel.span("compile", cat="dryrun", arch=arch,
                             shape=shape, mesh=mesh_name):
                lowered = jax.jit(step, in_shardings=shardings,
                                  donate_argnums=donate).lower(*args)
                compiled = lowered.compile()
            mem = compat.memory_stats(compiled)
            xla_cost = compiled.cost_analysis()
            # scan-aware per-device costs (XLA's cost_analysis counts while
            # bodies once — see analysis/hlo_cost.py); x chips = global.
            hlo_txt = compiled.as_text()
            pc = module_cost(hlo_txt)
            chips_ = mesh.devices.size
            cost = {"flops": pc.flops * chips_,
                    "bytes accessed": pc.bytes * chips_}
            coll = {k: v * chips_ for k, v in pc.collectives.items()}
            sh = SHAPES[shape]
            tokens = sh["seq_len"] * sh["global_batch"] if \
                sh["kind"] == "train" else sh["global_batch"]
            if sh["kind"] == "train":
                mflops = model_flops_train(bundle.active_param_count(),
                                           tokens)
            else:
                mflops = model_flops_decode(bundle.active_param_count(),
                                            tokens)
                if sh["kind"] == "prefill":
                    mflops = model_flops_train(
                        bundle.active_param_count(),
                        sh["seq_len"] * sh["global_batch"]) / 3.0  # fwd only
            result.update(
                status="ok",
                chips=chips,
                compile_s=round(time.time() - t0, 1),
                flops=cost.get("flops", 0.0),
                hlo_bytes=cost.get("bytes accessed", 0.0),
                collective_bytes=sum(coll.values()),
                collectives=coll,
                xla_flops_unscaled=xla_cost.get("flops", 0.0),
                model_flops=mflops,
                model_bytes=bundle.min_hbm_bytes(shape),
                memory_analysis={
                    "argument_size_gb": mem["argument_bytes"] / 1e9,
                    "output_size_gb": mem["output_bytes"] / 1e9,
                    "temp_size_gb": mem["temp_bytes"] / 1e9,
                    # peak_bytes = args + temps: donated outputs
                    # (params/opt/cache) alias their inputs on TPU (the
                    # CPU backend ignores donation, hence not args+temp+out)
                    "peak_gb_per_device": mem["peak_bytes"] / 1e9,
                },
            )
            tel.metrics.absorb(
                {"flops": result["flops"], "hlo_bytes": result["hlo_bytes"],
                 "collective_bytes": result["collective_bytes"],
                 "peak_bytes": mem["peak_bytes"]},
                prefix="dryrun.", arch=arch, shape=shape, mesh=mesh_name)
            print(f"[dryrun] {arch} x {shape} x {mesh_name}: OK "
                  f"({result['compile_s']}s, "
                  f"{result['memory_analysis']['peak_gb_per_device']:.2f} "
                  f"GB/dev)")
        except Exception as e:  # noqa: BLE001 — recorded, sweep continues
            result.update(status="error", error=f"{type(e).__name__}: {e}",
                          traceback=traceback.format_exc()[-2000:])
            print(f"[dryrun] {arch} x {shape} x {mesh_name}: "
                  f"FAIL {type(e).__name__}: {e}")
        finally:
            if ring:
                if prev_ring is None:
                    os.environ.pop("REPRO_RING_ATTN", None)
                else:
                    os.environ["REPRO_RING_ATTN"] = prev_ring

    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def roofline_from_cell(cell: dict) -> RooflineReport | None:
    if cell.get("status") != "ok":
        return None
    return RooflineReport(
        arch=cell["arch"], shape=cell["shape"], mesh=cell["mesh"],
        chips=cell["chips"], hlo_flops=cell["flops"],
        hlo_bytes=cell["hlo_bytes"], coll_bytes=cell["collective_bytes"],
        coll_breakdown=cell["collectives"], model_flops=cell["model_flops"],
        bytes_per_device=cell["memory_analysis"]["peak_gb_per_device"] * 1e9,
        model_bytes=cell.get("model_bytes", 0.0))


def _force_host_devices() -> None:
    """Give the CPU backend 512 virtual devices for the pod meshes.
    Merges, never clobbers: a caller that already forced a device count
    keeps it.  JAX reads XLA_FLAGS when the backend starts, so this must
    run before the first device query."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=512").strip()


def main() -> None:
    _force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--ring", default=None,
                    choices=["auto", "ring", "replicated", "off"],
                    help="pin the context-parallel attention mode for "
                         "every cell (default: ambient REPRO_RING_ATTN "
                         "policy)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace JSON of per-cell compile "
                         "spans + kernel dispatch instants")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot (per-cell flops/bytes "
                         "gauges) as JSON")
    args = ap.parse_args()

    tel = None
    if args.trace_out or args.metrics_out:
        import repro.obs as obs
        tel = obs.enable(process_name="dryrun")

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                r = run_cell(arch, shape, mesh_name, force=args.force,
                             ring=args.ring)
                s = r["status"]
                n_ok += s == "ok"
                n_skip += s == "skipped"
                n_err += s == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if tel is not None:
        if args.trace_out:
            print(f"[dryrun] trace -> {tel.write_trace(args.trace_out)}")
        if args.metrics_out:
            print(f"[dryrun] metrics -> "
                  f"{tel.write_metrics(args.metrics_out)}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""What every cell shares: finding a cell's files by name, the device
check, the compile counter, the peak table, percentiles and the result
line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found here by the name
``BENCHMARK.json`` gives it:

* configuration ``<c>``: ``bench/configs/<c>.json`` (sizes as run); its
  ``family`` names ``bench/families/<family>.py`` (weights in the
  program's layout, operation and byte counts) and
  ``bench/reference/<family>.py`` (the plain float32 reference);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by the one driver
  its ``kind`` names (``serve``);
* per-layer metric ``<m>``: ``bench/layers/<m>.py``, whose ``read(ctx)``
  returns a number or None.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (file names may hold '-' and '.')."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "configs", f"{name}.json")


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def layer_path(metric: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "layers", f"{metric}.py")


def family_module(family: str, root: str = ROOT):
    return load_module(os.path.join(root, "bench", "families", f"{family}.py"),
                       f"bench_family_{family}")


def reference_module(family: str, root: str = ROOT):
    return load_module(
        os.path.join(root, "bench", "reference", f"{family}.py"),
        f"bench_reference_{family}")


def layer_reader(metric: str, root: str = ROOT):
    return load_module(layer_path(metric, root),
                       "bench_layer_" + metric.replace(".", "_")
                       .replace("-", "_"))


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.root = root
        self.workload = cells[name]
        self.name = name
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(traffic_path(self.workload["traffic"], root))
        self.chips = int(self.workload["chips"])

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def family(self):
        return family_module(self.config["family"], self.root)

    def reference(self):
        return reference_module(self.config["family"], self.root)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

class NoAccelerator(RuntimeError):
    pass


def accelerator_devices(chips: int):
    """The chips a cell runs on.  No TPU, or fewer chips than the cell
    asks for, is an error: the benchmark never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def peaks_for(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``.  A kind that is not
    in ``bench/peaks.json`` is an error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "bench/peaks.json")
    return table[device_kind]


class CompileCounter:
    """Counts XLA programs compiled or loaded from the persistent cache
    (both pass JAX's backend-compile event) while ``armed``: a program
    that first appears inside the measured window is a warm-up fault."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def annotator(on: bool):
    """``name -> context``: a profiler annotation when tracing, else
    nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Profiler:
    """A few seconds of jax.profiler trace inside the window."""

    def __init__(self, root: str, name: str):
        self.dir = os.path.join(root, ".cache", "bench", "trace", name)
        self.t0 = self.t1 = None
        self.active = False

    def start(self, now):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t0 = now()
        self.active = True

    def stop(self, now):
        import jax
        self.t1 = now()
        jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> str:
        for d, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(d, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")


def spans_between(obs, t0, t1):
    """The program's telemetry events that lie wholly in [t0, t1]."""
    if obs is None:
        return []
    return [e for e in obs.tracer.events()
            if t0 <= e["ts"] and e["ts"] + e.get("dur", 0.0) <= t1]


# ---------------------------------------------------------------------------
# numbers
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[k - 1])


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, including those above
    32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The run's last stdout line; ``checks`` (each compared number beside
    its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)

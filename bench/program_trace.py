#!/usr/bin/env python3
"""Read what the program itself puts into a run: its telemetry spans and
the names its device work carries in a profiler trace (``.xplane.pb``).

``trace_reduce`` gives busy time, self time per device op and the idle
gaps, named by the harness's annotations.  This module reads the rest:

* the program's host spans: ``repro.obs`` mirrors each telemetry span
  into the profiler as an annotation of the same name, on the ``/host:``
  planes beside the harness's own (:data:`PROGRAM_SPANS`);
* each device op's framework op name: the ``tf_op`` stat of the op's
  event metadata in the device plane (``jit(f)/.../ssd/dot_general``),
  which holds the model's named scopes (:data:`SCOPES`).  ``ProfileData``
  does not expose event metadata, so it is read from the XSpace wire
  format here, in plain Python;
* the executions of the decode program: the events of the device plane's
  ``XLA Modules`` line (one per execution of a step program) that hold an
  op event of the Pallas paged decode kernel (:data:`DECODE_KERNEL`);
* from the telemetry itself (not the trace): the program-load spans
  ``jit.trace``, ``jit.lower`` and ``jit.compile``.

Idle intervals are those of ``trace_reduce`` in the same window (the span
of the harness annotations).  Each is put down to the innermost program
span open at its midpoint, else to the innermost harness annotation, else
to :data:`OUTSIDE`.  Device time per scope is the self time of the ops in
the window, keyed by their whole instruction: an op name such as
``%fusion.12`` recurs in every step program, with another scope in each.

    python3 bench/program_trace.py <trace.xplane.pb>

prints the idle time by span and the device time by scope, then the top
device ops with their framework op names and the decode executions.
"""
from __future__ import annotations

import bisect
import heapq
import os
import statistics
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

PROGRAM_SPANS = ("admission", "prefix_match", "cow", "reclaim", "prefill",
                 "decode", "rows.build", "rows.launch", "rows.wait",
                 "rows.commit")
SCOPES = ("qkv", "attention", "mlp", "lm_head", "in_proj", "conv", "ssd",
          "gate_norm", "out_proj", "loss", "adamw")
JIT_SPANS = ("jit.trace", "jit.lower", "jit.compile")
DECODE_KERNEL = "%paged_flash_decode"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside"
DEVICE = "/device:TPU:"

# ---------------------------------------------------------------------------
# XSpace wire format: planes -> event metadata -> the ``tf_op`` stat
# ---------------------------------------------------------------------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value =
# 5, .ref_value = 7 (the id of a stat metadata whose name is the string).


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, i=0, end=None):
    """(field number, value) of each field of one message in
    ``buf[i:end]``: an int for a varint, (start, end) of the payload for a
    length-delimited field; fixed-width fields are skipped."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def framework_ops(path: str) -> dict[str, str]:
    """Each device op's whole instruction (the name its events carry) ->
    its framework op name, the ``tf_op`` stat without its type suffix."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                key, meta = _map_entry(buf, v)
                for mf, mv in _fields(buf, *meta):
                    if mf == 2:
                        stat_names[key] = _text(buf, mv)
        if not name.startswith(DEVICE):
            continue
        for entry in events:
            _, meta = _map_entry(buf, entry)
            op, tf_op = None, None
            for mf, mv in _fields(buf, *meta):
                if mf == 2:
                    op = _text(buf, mv)
                elif mf == 5:
                    tf_op = _tf_op(buf, mv, stat_names) or tf_op
            if op and tf_op:
                out[op] = tf_op.rsplit(":", 1)[0]
    return out


def _tf_op(buf, stat, stat_names):
    sid, value = None, None
    for f, v in _fields(buf, *stat):
        if f == 1:
            sid = v
        elif f == 5:
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v)
    return value if stat_names.get(sid) == "tf_op" else None


def scope_of(framework_name: str | None) -> str | None:
    """The innermost of :data:`SCOPES` in a framework op name, reading
    through transformations: ``jit(f)/transpose(jvp(ssd))/mul`` -> ssd."""
    for part in reversed((framework_name or "").split("/")):
        words = part.replace("(", " ").replace(")", " ").split()
        hits = [w for w in words if w in SCOPES]
        if hits:
            return hits[-1]
    return None


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

class ProgramTrace:
    """A trace's device ops and step-program executions per device plane,
    and its host events (harness annotations and program spans); events
    are (name, start_ns, end_ns)."""

    def __init__(self, ops: dict, modules: dict, host: list,
                 framework: dict):
        self.ops = ops                  # device plane -> op events
        self.modules = modules          # device plane -> program executions
        self.framework = framework      # instruction -> framework op name
        self.spans = [e for e in host if e[0] in PROGRAM_SPANS]
        marks = [e for e in host if e[0] in trace_reduce.ANNOTATIONS]
        if not marks:
            raise ValueError("the trace holds no harness annotation")
        self.window = (min(s for _, s, _ in marks),
                       max(e for _, _, e in marks))
        self._spans = _innermost(self.spans)
        self._marks = _innermost(marks)

    @classmethod
    def read(cls, path: str) -> "ProgramTrace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops, modules, host = {}, {}, []
        wanted = set(PROGRAM_SPANS) | set(trace_reduce.ANNOTATIONS)
        for plane in pd.planes:
            if plane.name.startswith(DEVICE):
                ops[plane.name], modules[plane.name] = [], []
                for line in plane.lines:
                    if line.name == trace_reduce.OPS_LINE:
                        ops[plane.name] += [(e.name, e.start_ns, e.end_ns)
                                            for e in line.events]
                    elif line.name == MODULES_LINE:
                        modules[plane.name] += [(e.name, e.start_ns,
                                                 e.end_ns)
                                                for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in wanted]
        if not ops:
            raise ValueError("the trace holds no device plane")
        return cls(ops, modules, host, framework_ops(path))

    def gaps(self) -> list[tuple[float, float]]:
        """(start_ns, end_ns) of every idle interval of every device in
        the window."""
        w0, w1 = self.window
        out = []
        for evs in self.ops.values():
            merged = trace_reduce._union(
                [(max(s, w0), min(e, w1)) for _, s, e in evs
                 if e > w0 and s < w1])
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            out += [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
        return out

    def cause(self, t: float) -> str:
        """The innermost program span open at ``t``, else the innermost
        harness annotation, else :data:`OUTSIDE`."""
        return _at(self._spans, t) or _at(self._marks, t) or OUTSIDE

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds per cause (:meth:`cause` at each interval's
        midpoint), averaged over the devices."""
        out = defaultdict(float)
        for a, b in self.gaps():
            out[self.cause((a + b) / 2)] += (b - a) * 1e-9 / len(self.ops)
        return dict(out)

    def op_seconds(self) -> dict[str, float]:
        """Device self time per op, keyed by its whole instruction, in
        the window, averaged over the devices."""
        w0, w1 = self.window
        out = defaultdict(float)
        for evs in self.ops.values():
            clipped = [(max(s, w0), min(e, w1), n) for n, s, e in evs
                       if e > w0 and s < w1]
            for n, t in trace_reduce._self_times(clipped):
                out[n] += t * 1e-9 / len(self.ops)
        return dict(out)

    def scope_seconds(self) -> dict[str | None, float]:
        """Device self time per named scope in the window (None: ops in
        no scope), averaged over the devices."""
        out = defaultdict(float)
        for n, t in self.op_seconds().items():
            out[scope_of(self.framework.get(n))] += t
        return dict(out)

    def decode_executions(self) -> list[float]:
        """Device seconds of each step-program execution that holds an op
        event of :data:`DECODE_KERNEL`."""
        out = []
        for plane, runs in self.modules.items():
            kernel = sorted((s, e) for n, s, e in self.ops.get(plane, [])
                            if trace_reduce.op_name(n)
                            .startswith(DECODE_KERNEL))
            starts = [s for s, _ in kernel]
            for _, s, e in runs:
                i = bisect.bisect_left(starts, s)
                if i < len(kernel) and kernel[i][1] <= e:
                    out.append((e - s) * 1e-9)
        return out


def _innermost(events):
    """[(start, end, name)] pieces of time, each labelled by the
    innermost (latest-starting) event open in it."""
    events = sorted(events, key=lambda e: e[1])
    cuts = sorted({x for _, s, e in events for x in (s, e)})
    out, heap, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(events) and events[k][1] <= a:
            n, s, e = events[k]
            heapq.heappush(heap, (-s, e, n))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def _at(pieces, t):
    i = bisect.bisect_right(pieces, (t, float("inf"), "")) - 1
    if i >= 0 and pieces[i][0] <= t <= pieces[i][1]:
        return pieces[i][2]
    return None


def of(ctx: dict) -> ProgramTrace:
    """The trace of a per-layer context, read once and kept in it."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = ProgramTrace.read(ctx["xplane"])
    return ctx["program_trace"]


# ---------------------------------------------------------------------------
# the numbers the per-layer metrics read
# ---------------------------------------------------------------------------

def median_decode_ms(trace: ProgramTrace) -> float | None:
    runs = trace.decode_executions()
    return 1e3 * statistics.median(runs) if runs else None


def scope_share(trace: ProgramTrace, scope: str,
                busy_s: float) -> float | None:
    """Percent of the devices' busy time spent in ops under ``scope``;
    None when no op of the trace is under it."""
    seconds = trace.scope_seconds().get(scope)
    if not seconds or busy_s <= 0:
        return None
    return 100.0 * seconds / busy_s


def view_live_share(spans: list) -> float | None:
    """Percent of the decode calls' page views that rows attend: summed
    ``live_keys`` over summed ``view_keys`` of the program's ``decode``
    spans; None when no span carries them."""
    decode = [e["args"] for e in spans if e.get("ph") == "X"
              and e["name"] == "decode" and "view_keys" in e.get("args", {})]
    view = sum(a["view_keys"] for a in decode)
    if not view:
        return None
    return 100.0 * sum(a["live_keys"] for a in decode) / view


def jit_phases(events: list, t0: float = float("-inf"),
               t1: float = float("inf")) -> dict:
    """The program-load spans (:data:`JIT_SPANS`) that lie in [t0, t1]:
    seconds and count per phase of the outermost ones (a function traced
    while another is traced, or a constant compiled while tracing, counts
    in the outer span only), and ``total_s``, the time any of them
    covered."""
    spans = sorted(((e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                    if e.get("ph") == "X" and e["name"] in JIT_SPANS
                    and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1))
    out = {name.split(".")[1]: {"s": 0.0, "n": 0} for name in JIT_SPANS}
    total, end = 0.0, float("-inf")
    for s, e, name in spans:
        if s >= end:
            phase = out[name.split(".")[1]]
            phase["s"] += e - s
            phase["n"] += 1
        total += max(0.0, e - max(s, end))
        end = max(end, e)
    out["total_s"] = total
    return out


def setup_jit_seconds(events: list, spans: list) -> float | None:
    """Time the program spent loading programs (tracing, lowering,
    compiling or loading them from the persistent cache) before the first
    of ``spans`` (the window's traced spans): ``jit_phases(...)["total_s"]``
    up to there; None when either holds nothing."""
    if not spans:
        return None
    first = min(e["ts"] for e in spans)
    phases = jit_phases(events, t1=first)
    if not any(phases[p.split(".")[1]]["n"] for p in JIT_SPANS):
        return None
    return phases["total_s"]


def global_events() -> list:
    """The events of the program's process-global telemetry."""
    from repro import obs
    return obs.get_telemetry().tracer.events()


# ---------------------------------------------------------------------------
# the operator's view
# ---------------------------------------------------------------------------

def report(path: str, top: int = 15) -> str:
    trace = ProgramTrace.read(path)
    w0, w1 = trace.window
    window = (w1 - w0) * 1e-9
    idle = trace.idle_by_span()
    total_idle = sum(idle.values())
    lines = [f"window {window:.4f} s, idle {total_idle:.4f} s "
             f"({100 * total_idle / window:.2f}%)", "",
             f"{'idle by span':<24}{'s':>12}{'% window':>10}{'% idle':>8}"]
    for cause, s in sorted(idle.items(), key=lambda x: -x[1]):
        lines.append(f"{cause:<24}{s:>12.6f}{100 * s / window:>10.3f}"
                     f"{100 * s / max(total_idle, 1e-12):>8.2f}")
    scopes = trace.scope_seconds()
    busy = sum(scopes.values())
    lines += ["", f"{'device time by scope':<24}{'s':>12}{'% busy':>10}"]
    for scope, s in sorted(scopes.items(), key=lambda x: -x[1]):
        lines.append(f"{scope or '(no scope)':<24}{s:>12.6f}"
                     f"{100 * s / max(busy, 1e-12):>10.2f}")
    ops = sorted(trace.op_seconds().items(), key=lambda x: -x[1])[:top]
    lines += ["", f"{'top device ops':<40}{'s':>12}  framework op"]
    for op, s in ops:
        lines.append(f"{trace_reduce.op_name(op):<40}{s:>12.6f}  "
                     f"{trace.framework.get(op, '')}")
    runs = trace.decode_executions()
    if runs:
        lines += ["", f"decode executions {len(runs)}, median "
                  f"{1e3 * statistics.median(runs):.3f} ms"]
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(f"usage: {sys.argv[0]} <trace.xplane.pb>")
    print(report(sys.argv[1]))

"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time (the union of the intervals in which
an operation ran, averaged over the chips), per-operation device time,
and the idle gaps, each named by the harness annotation the host was in.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events of the line named ``XLA Ops``.  An event's name there is the
whole HLO instruction; an operation is named by the instruction's name
(``%paged_flash_decode.5``).  A loop's event holds the events of its body,
so an operation's time is its self time: its duration less that of the
events nested in it.  Host annotations are the events of the host plane
whose names are the harness's own (:data:`ANNOTATIONS`).  The window is
the span of those annotations, and busy time and gaps are clipped to it.
"""
from __future__ import annotations

from collections import defaultdict

ANNOTATIONS = ("submit", "engine.step", "harvest", "next_batch",
               "train_step")
OPS_LINE = "XLA Ops"


def load(path: str):
    """(device op events per device, host annotation events) from an
    xplane file; events are (name, start_ns, end_ns, stats dict)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), e.start_ns, e.end_ns, {})
                            for e in line.events]
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns, {})
                         for e in line.events if e.name in ANNOTATIONS]
    return devices, host


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return hlo.split(" = ", 1)[0].strip()


def _self_times(evs):
    """(name, self seconds-in-ns) of each event, nested events taken out
    of their parents' time."""
    order = sorted(evs, key=lambda e: (e[0], -e[1]))
    out, stack = [], []              # stack of [end, name, self]
    for s, e, n in order:
        while stack and stack[-1][0] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, n, e - s])
    out += stack
    return [(n, t) for _, n, t in out]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_events(devices: dict, host: list) -> dict:
    """The reduction itself, on events as :func:`load` returns them."""
    if not host:
        raise ValueError("the trace holds no harness annotation")
    w0 = min(s for _, s, _, _ in host)
    w1 = max(e for _, _, e, _ in host)
    window_ns = w1 - w0
    busy, ops, gaps = [], defaultdict(float), []
    for name, evs in sorted(devices.items()):
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e, _ in evs
                   if e > w0 and s < w1]
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        for n, t in _self_times(clipped):
            ops[n] += t / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_at(host, (a + b) / 2)))
    if not busy:
        raise ValueError("the trace holds no device plane")
    gaps.sort(key=lambda g: -g[0])
    device_ops = sorted(([n, t * 1e-9] for n, t in ops.items()),
                        key=lambda x: -x[1])
    return {"window_s": window_ns * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "device_ops": device_ops,
            "idle_gaps": [[n, d * 1e-9] for d, n in gaps]}


def _host_at(host, t):
    """The innermost (latest-starting) harness annotation open at t."""
    inside = [(s, n) for n, s, e, _ in host if s <= t <= e]
    return max(inside)[1] if inside else "host:outside harness calls"


def reduce(path: str) -> dict:
    return reduce_events(*load(path))


def kernel_seconds(reduction: dict, *needles: str) -> float | None:
    """Summed device time of the operations whose names hold every needle;
    None when no operation matches."""
    hits = [s for n, s in reduction["device_ops"]
            if all(k in n for k in needles)]
    return sum(hits) if hits else None

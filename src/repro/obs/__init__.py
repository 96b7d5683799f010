"""Unified telemetry: metrics registry + span tracer + traffic accounting.

Three layers (see docs/ARCHITECTURE.md "Observability"):

1. :mod:`repro.obs.metrics` — labeled counters/gauges/histograms with one
   process-wide :data:`~repro.obs.metrics.REGISTRY` that absorbs the
   repo's scattered ad-hoc stats;
2. :mod:`repro.obs.trace` — nested span tracer (virtual-clock compatible)
   with a Chrome trace-event JSON exporter perfetto can load; each span
   is also mirrored into the JAX profiler's trace when JAX is loaded;
3. :mod:`repro.obs.roofline_live` — observed-vs-predicted traffic rows
   that close the loop on the paper's fetch-reduction claims at runtime.

The :class:`Telemetry` facade bundles a tracer with the registry and a
single ``enabled`` switch.  The GLOBAL default is DISABLED: hot paths
(the serving tick loop) check ``telemetry.enabled`` once and skip every
span/counter, so an untelemetered serve pays only a handful of attribute
reads per tick (< 2% tick time — asserted by the smoke benchmark).
``obs.enable()`` flips the global on (the launchers do this when
``--trace-out``/``--metrics-out`` is passed); components that cannot be
handed a Telemetry explicitly (kernel wrappers, checkpoint manager) reach
it through :func:`get_telemetry`.

Program loads: while an enabled telemetry is installed, JAX's compile
events become spans ``jit.trace`` (tracing a function to a jaxpr),
``jit.lower`` (jaxpr to an MLIR module) and ``jit.compile`` (the backend
compile, or the load of the executable from the persistent cache), each
with the arg ``fun``, and the persistent cache's answers become the
counters ``jit.cache_hits`` and ``jit.cache_misses``.  The listeners are
registered once per process, by :func:`set_telemetry` with an enabled
telemetry, and only if JAX is already imported.

The package is deliberately jax-free so the host-side control modules
that import it stay jax-free too: it imports nothing of JAX itself.
"""
from __future__ import annotations

import json
import os
import sys
import time
from contextlib import contextmanager
from typing import Any, Callable

from .metrics import REGISTRY, MetricsRegistry
from .trace import SpanTracer


@contextmanager
def _noop_span(*_a, **_kw):
    yield None


class Telemetry:
    """A tracer + the metrics registry behind one enabled/disabled switch.

    ``span``/``instant`` delegate to the tracer when enabled and are
    no-ops otherwise; ``metrics`` is always the (cheap, ever-live)
    registry — components use ``telemetry.enabled`` to gate per-tick
    hot-loop recording and push rare events unconditionally.
    """

    def __init__(self, *, enabled: bool = True,
                 registry: MetricsRegistry | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 process_name: str = "repro"):
        self.enabled = enabled
        self.metrics = registry if registry is not None else REGISTRY
        self.tracer = SpanTracer(clock, process_name=process_name)

    # -- recording (gated) --------------------------------------------------

    def span(self, name: str, cat: str = "span", **args):
        if not self.enabled:
            return _noop_span()
        return self.tracer.span(name, cat, **args)

    def begin(self, name: str, cat: str = "span", **args):
        return self.tracer.begin(name, cat, **args) if self.enabled else None

    def finish(self, handle, **extra) -> None:
        if handle is not None:
            self.tracer.finish(handle, **extra)

    def instant(self, name: str, cat: str = "event", **args) -> None:
        if self.enabled:
            self.tracer.instant(name, cat, **args)

    def counter(self, name: str, value: float = 1, **labels) -> None:
        if self.enabled:
            self.metrics.counter(name, value, **labels)

    # -- artifacts ----------------------------------------------------------

    def snapshot(self) -> dict:
        return self.metrics.snapshot()

    def write_trace(self, path: str) -> str:
        return self.tracer.write_chrome_trace(path)

    def write_metrics(self, path: str, extra: dict[str, Any] | None = None
                      ) -> str:
        """Write ``snapshot()`` (plus optional caller context) as JSON."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        snap = self.snapshot()
        if extra:
            snap = {**snap, **extra}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True, default=str)
        os.replace(tmp, path)
        return path


_DISABLED = Telemetry(enabled=False)
_default: Telemetry = _DISABLED


def get_telemetry() -> Telemetry:
    """The process-global telemetry (disabled until :func:`enable`)."""
    return _default


def set_telemetry(t: Telemetry | None) -> Telemetry:
    """Install ``t`` as the global (None restores the disabled default);
    returns the previous one so scopes can put it back.  An enabled ``t``
    in a process that has imported JAX also starts the program-load
    listeners (:class:`_JitListener`)."""
    global _default
    prev = _default
    _default = t if t is not None else _DISABLED
    if _default.enabled and "jax" in sys.modules:
        _JitListener.install()
    return prev


class _JitListener:
    """Turns JAX's compile events into spans and counters of the global
    telemetry, once it is enabled and clocked by ``time.monotonic``.

    JAX stamps its spans with ``time.time()``; the offset to
    ``time.monotonic()`` is taken once, when the listener is made.  A
    telemetry on another clock (the train launcher's per-step virtual
    clock, whose traces replay bit for bit) records none of them."""

    SPANS = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
             "/jax/core/compile/backend_compile_duration": "jit.compile"}
    COUNTERS = {"/jax/compilation_cache/cache_hits": "jit.cache_hits",
                "/jax/compilation_cache/cache_misses": "jit.cache_misses"}

    installed: "_JitListener | None" = None

    def __init__(self):
        self.offset = time.monotonic() - time.time()

    @classmethod
    def install(cls) -> None:
        if cls.installed is not None:
            return
        import jax.monitoring
        cls.installed = listener = cls()
        jax.monitoring.register_event_time_span_listener(listener.on_span)
        jax.monitoring.register_event_listener(listener.on_event)

    @staticmethod
    def _records(t: Telemetry, event: str, names: dict) -> bool:
        return (t.enabled and event in names
                and t.tracer.clock is time.monotonic)

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        t = _default
        if self._records(t, event, self.SPANS):
            t.tracer.complete(self.SPANS[event], start + self.offset,
                              end + self.offset, cat="jit",
                              fun=str(kw.get("fun_name", "")))

    def on_event(self, event: str, **_) -> None:
        t = _default
        if self._records(t, event, self.COUNTERS):
            t.counter(self.COUNTERS[event])


def enable(*, clock: Callable[[], float] = time.monotonic,
           process_name: str = "repro") -> Telemetry:
    """Install and return a fresh ENABLED global telemetry."""
    t = Telemetry(enabled=True, clock=clock, process_name=process_name)
    set_telemetry(t)
    return t


__all__ = ["REGISTRY", "MetricsRegistry", "SpanTracer", "Telemetry",
           "enable", "get_telemetry", "set_telemetry"]

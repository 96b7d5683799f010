"""Seconds of set-up the program spent loading its programs: the time
covered by the ``jit.trace``, ``jit.lower`` and ``jit.compile`` spans of
its global telemetry (tracing, lowering, and compiling or loading from
the persistent cache; a span nested in another counted once) that end
before the first of the window's traced spans.  The window compiles
nothing, so these are set-up's; the reference check's compiles come after
the window.  None for a program that records no such spans
(``program_trace.setup_jit_seconds``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import program_trace  # noqa: E402


def read(ctx):
    return program_trace.setup_jit_seconds(program_trace.global_events(),
                                           ctx["spans"])

"""Share of the device's busy time in the traced window spent in
collective ops (``collective-permute``, ``all-gather``,
``reduce-scatter``, ``all-reduce``, ``all-to-all``, each whole or as its
-start/-done halves, and the TPU compiler's ``async-collective``
halves) by their self time on the ``XLA Ops`` line, averaged over the
chips, in percent: collective time that compute does not hide.  None
when the trace holds no collective."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _device_ops  # noqa: E402
import program_trace  # noqa: E402


def read(ctx):
    seconds = _device_ops.collective_seconds(program_trace.of(ctx))
    busy = ctx["reduction"]["busy_s"]
    if not seconds or busy <= 0:
        return None
    return 100.0 * seconds / busy

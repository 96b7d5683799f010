"""Device self time of the ops of a traced window picked by what they
are, averaged over the chips (``program_trace.ProgramTrace.op_seconds``):
the collectives, by the HLO op's name, and the ops under a named scope,
by the framework op name their metadata carries (``tf_op``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce  # noqa: E402

# XLA's collective ops, synchronous or split into -start/-done halves;
# the TPU compiler's async collective fusions (a matmul's gather or
# scatter) are named async-collective-start/-done in a v5e trace
COLLECTIVES = ("collective-permute", "all-gather", "reduce-scatter",
               "all-reduce", "all-to-all", "async-collective")


def collective_seconds(trace) -> float:
    """Self time of the collective ops on the ``XLA Ops`` line: the time a
    chip spends in a collective or waiting for one to finish, which no
    compute hides."""
    return sum(t for n, t in trace.op_seconds().items()
               if any(c in trace_reduce.op_name(n) for c in COLLECTIVES))


def under_scope(framework_name, scope: str) -> bool:
    """Whether ``scope`` is a component of a framework op name, read
    through transformations (``transpose(jvp(ring_hop))``)."""
    for part in (framework_name or "").split("/")[:-1]:
        if scope in part.replace("(", " ").replace(")", " ").split():
            return True
    return False


def scope_seconds(trace, scope: str) -> float:
    """Self time of the ops under the named scope ``scope``."""
    return sum(t for n, t in trace.op_seconds().items()
               if under_scope(trace.framework.get(n), scope))

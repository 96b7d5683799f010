"""Roofline share of the ring hop: the least time one chip needs for the
hops of the steps that ran wholly inside the trace (the larger of the
family's ``ring_hop_cost`` FLOPs over the bf16 peak and its bytes over
the HBM bandwidth, per step, times the steps), over the device self time
of the ops under the ``ring_hop`` named scope (each hop's Pallas fold,
its combine and its ppermutes, in the forward, the recomputed forward
and the backward ring), averaged over the chips, in percent.  None for a
program whose ops carry no such scope."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _device_ops  # noqa: E402
import program_trace  # noqa: E402

SCOPE = "ring_hop"


def read(ctx):
    steps = len(ctx["steps_traced"])
    seconds = _device_ops.scope_seconds(program_trace.of(ctx), SCOPE)
    if not steps or not seconds:
        return None
    t = ctx["traffic"]
    flops, nbytes = ctx["family"].ring_hop_cost(
        ctx["config"], t["batch"], t["seq_len"],
        ctx["config"]["deployment"]["chips"])
    p = ctx["peaks"]
    least = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * steps * least / seconds

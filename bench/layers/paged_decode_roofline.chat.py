"""Roofline share of the Pallas paged decode kernel: the least time the
chip needs for the attention of the decode rows served in the traced
window (the larger of the configuration's FLOPs over the bf16 peak and
its bytes over the HBM bandwidth), over the kernel's summed device time
in the trace, in percent."""
import trace_reduce

KERNEL = "paged_flash_decode"


def read(ctx):
    traced = ctx["traced"]
    seconds = trace_reduce.kernel_seconds(ctx["reduction"], KERNEL)
    if seconds is None or not traced.decode_rows:
        return None
    flops, nbytes = ctx["family"].paged_decode_cost(
        ctx["config"], traced.decode_rows, traced.decode_keys)
    p = ctx["peaks"]
    least = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds

"""Share of the device's busy time in the traced window spent in the
operations under the Mamba-2 ``ssd`` named scope (the chunked scan:
forward, recomputation and backward), by their self time, in percent.
None for a program whose ops carry no such scope."""
import program_trace


def read(ctx):
    return program_trace.scope_share(program_trace.of(ctx), "ssd",
                                     ctx["reduction"]["busy_s"])

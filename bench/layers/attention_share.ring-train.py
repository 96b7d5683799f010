"""Share of the device's busy time in the traced window spent in the ops
under the ``attention`` named scope of the training layer (the ring, its
reshards and the output projection; forward, recomputation and backward),
by their self time, averaged over the chips, in percent.  None for a
program whose training ops carry no such scope."""
import program_trace


def read(ctx):
    return program_trace.scope_share(program_trace.of(ctx), "attention",
                                     ctx["reduction"]["busy_s"])

"""Median device time of one execution of the decode step program, in
milliseconds: the events of the device plane's ``XLA Modules`` line that
hold an op event of the Pallas paged decode kernel
(``program_trace.median_decode_ms``)."""
import program_trace


def read(ctx):
    return program_trace.median_decode_ms(program_trace.of(ctx))

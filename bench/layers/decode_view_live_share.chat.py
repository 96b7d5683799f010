"""Share of the decode calls' attention page views that rows attend: the
engine's ``live_keys`` over its ``view_keys`` (slots x pages of the view x
page size), summed over the program's ``decode`` spans inside the trace,
in percent (``program_trace.view_live_share``).  None for a program whose
``decode`` spans carry no view counts."""
import program_trace


def read(ctx):
    return program_trace.view_live_share(ctx["spans"])

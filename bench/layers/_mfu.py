"""Model FLOP utilisation of the serving step over the traced window:
the model FLOPs of the tokens the steps computed (prefill chunks and
decode rows; not padding, not prefix-cache hits; the engine's
``written_tokens``), from the configuration's counts, over the window
times the chip's bf16 peak, in percent.  Attention is counted from the
engine's ``gb_read_tokens``, one read of its context a row: exact for a
decode row, one query for a prefill chunk, so a lower bound there."""


def read(ctx):
    traced = ctx["traced"]
    if not traced.computed:
        return None
    flops = ctx["family"].token_flops(ctx["config"], traced.computed,
                                      traced.keys_read)
    return 100.0 * flops / (ctx["reduction"]["window_s"]
                            * ctx["peaks"]["bf16_flops_per_s"])

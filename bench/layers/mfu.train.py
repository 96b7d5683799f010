"""Model FLOP utilisation of the train step over the traced window: the
forward and backward FLOPs per token (no recomputation, from the
configuration's counts) times the tokens of the steps that ran wholly
inside the trace, over the time from the first of them starting to the
last ending, over the chip's bf16 peak, in percent.  The float32 matrix
products run at the default precision, one bf16 pass, so the bf16 peak is
the one that bounds them."""


def read(ctx):
    steps = ctx["steps_traced"]
    if not steps:
        return None
    span = steps[-1][1] - steps[0][0]
    flops = (ctx["family"].train_flops_per_token(ctx["config"])
             * ctx["tokens_per_step"] * len(steps))
    return 100.0 * flops / (span * ctx["peaks"]["bf16_flops_per_s"])

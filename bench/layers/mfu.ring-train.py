"""Model FLOP utilisation of the four-chip train step over the traced
window: the forward and backward FLOPs per token (no recomputation,
``train_flops_per_token`` of the configuration at the cell's sequence
length) times the tokens of the steps that ran wholly inside the trace,
over the time from the first of them starting to the last ending, over
the deployment's chips times one chip's bf16 peak, in percent.  The
float32 matrix products run at the default precision, one bf16 pass, so
the bf16 peak is the one that bounds them."""


def read(ctx):
    steps = ctx["steps_traced"]
    if not steps:
        return None
    span = steps[-1][1] - steps[0][0]
    flops = (ctx["family"].train_flops_per_token(
        ctx["config"], ctx["traffic"]["seq_len"])
        * ctx["tokens_per_step"] * len(steps))
    chips = ctx["config"]["deployment"]["chips"]
    return 100.0 * flops / (span * chips * ctx["peaks"]["bf16_flops_per_s"])

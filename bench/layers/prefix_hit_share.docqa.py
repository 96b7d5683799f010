"""Share of the prompt tokens served in the whole window that the radix
prefix cache served: the engine's matched tokens (``prefix_stats``) over
those plus the prompt tokens the steps computed (``traffic_stats``
written tokens less the decode rows), in percent."""


def read(ctx):
    served = ctx["served"]
    prompt = served.matched + served.prompt_computed
    if not prompt:
        return None
    return 100.0 * served.matched / prompt

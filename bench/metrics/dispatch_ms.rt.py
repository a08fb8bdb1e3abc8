"""Host milliseconds inside the ingress commit that enqueues one tick,
summed over the traced slice and divided by its ticks."""


def read(ctx):
    if not ctx.commit_s:
        return None
    return 1e3 * sum(ctx.commit_s) / len(ctx.commit_s)

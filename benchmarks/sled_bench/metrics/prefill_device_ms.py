"""Mean device time of one admission in the traced window, in
milliseconds: the prefill program plus the eager scatters that write the
prefilled row into the pool, over the number of prefills."""

PREFILL = "jit_prefill_step"
POOL_WRITE = "jit_scatter"


def read(ctx):
    if ctx.trace is None:
        return None
    pre = ctx.trace.module_seconds(PREFILL)
    if not pre:
        return None
    return 1e3 * (sum(pre) + sum(ctx.trace.module_seconds(POOL_WRITE))) / len(pre)

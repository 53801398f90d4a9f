"""Mean device time of one run of the verify program (``paged_verify_step``)
in the traced window, in milliseconds."""

PROGRAM = "jit_paged_verify_step"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_seconds(PROGRAM)
    return 1e3 * sum(runs) / len(runs) if runs else None

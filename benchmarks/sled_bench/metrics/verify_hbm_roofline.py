"""Bytes the verify rounds of the traced window needed (``cost.round_work``: the
weights once per round, live K/V of real requests, their fresh rows) over
the device time of the verify program times the chip's peak HBM
bandwidth, in percent.  The pool copy and reads past a row's live length
are not needed work, so removing them raises this share."""

import cost

PROGRAM = "jit_paged_verify_step"


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    busy = sum(ctx.trace.module_seconds(PROGRAM))
    if busy <= 0:
        return None
    nbytes = sum(cost.round_work(ctx.shapes, r.requests)[1] for r in ctx.traced_rounds)
    return 100.0 * nbytes / (busy * cost.peak(ctx.device_kind)["hbm_bytes_per_s"])

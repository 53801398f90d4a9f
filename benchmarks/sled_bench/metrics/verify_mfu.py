"""FLOPs the verify rounds of the traced window needed (``cost.round_work``: real
requests only, attention over live lengths) over the device time of the
verify program times the chip's peak bf16 rate, in percent."""

import cost

PROGRAM = "jit_paged_verify_step"


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    busy = sum(ctx.trace.module_seconds(PROGRAM))
    if busy <= 0:
        return None
    flops = sum(cost.round_work(ctx.shapes, r.requests)[0] for r in ctx.traced_rounds)
    return 100.0 * flops / (busy * cost.peak(ctx.device_kind)["bf16_flops_per_s"])

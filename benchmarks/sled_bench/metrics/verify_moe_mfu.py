"""FLOPs the verify rounds of the traced window needed, for a latent-attention
MoE configuration (``cost_mla_moe.round_work``: real requests, live
lengths, the held experts' expected share of each token), over the device
time of the verify program times the chip's peak bf16 rate, in percent.
None for other configurations."""

import cost
import cost_mla_moe

PROGRAM = "jit_paged_verify_step"


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    sh = cost_mla_moe.shapes_for(ctx)
    busy = sum(ctx.trace.module_seconds(PROGRAM))
    if sh is None or busy <= 0:
        return None
    flops = sum(cost_mla_moe.round_work(sh, r.requests)[0] for r in ctx.traced_rounds)
    return 100.0 * flops / (busy * cost.peak(ctx.device_kind)["bf16_flops_per_s"])

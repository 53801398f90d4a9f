"""Bytes the verify rounds of the traced window needed, for a latent-attention
MoE configuration (``cost_mla_moe.round_work``: the weights once per
round, each held expert's by the chance the round's tokens hit it, the
latent rows of real requests' live positions and their fresh rows), over
the device time of the verify program times the chip's peak HBM bandwidth,
in percent.  None for other configurations."""

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
    nbytes = sum(cost_mla_moe.round_work(sh, r.requests)[1] for r in ctx.traced_rounds)
    return 100.0 * nbytes / (busy * cost.peak(ctx.device_kind)["hbm_bytes_per_s"])

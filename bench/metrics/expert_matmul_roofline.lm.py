"""Roofline share of the expert layers' grouped matmuls (device trace): the
least time for their operations and bytes in the traced window over the
device time of the ops under the model's ``fl.moe.experts`` scope.

The operations are the useful ones, ``expert_slots`` (the token-slots the
held experts computed in the window, a device counter of the program) ×
2 × 3 × hidden × expert width × ``expert_matmul_passes``; the bytes, each
pass of each layer call reading the held experts' bf16 weights, and each
slot's rows (``flops_gossip_lm.expert_matmul_cost``).  Padding inside the
kernel is not counted, so it cannot push the share up."""

import flops_gossip_lm
import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    c = ctx.counters
    if pt is None or ctx.peaks is None or "expert_slots" not in c:
        return None
    secs = pt.stage_seconds().get("fl.moe.experts")
    if not secs:
        return None
    ops, nbytes = flops_gossip_lm.expert_matmul_cost(
        ctx.config, c["expert_slots"], c["expert_layer_calls"], c["expert_matmul_passes"])
    share, _ = ctx.flops.roofline_share(ops, nbytes, secs, ctx.peaks["bf16_flops_per_s"],
                                        ctx.peaks["hbm_bytes_per_s"])
    return share

"""Roofline share of the Pallas gossip mix (device trace): the least time
for the operations and bytes of each all-receivers mix in the traced
window (``flops.mix_cost`` of users x parameters per user, unpadded), over
the kernel's summed device time.  Memory-bound: the bound is HBM
bandwidth.  The kernel is the Pallas custom call that maps the (users, L)
stack and the (users, users) mixing matrix to a (users, L) result."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr = ctx.tr
    if tr is None or ctx.peaks is None:
        return None
    u = ctx.counters["users"]
    ev = [e for e in tr.ops_matching(
        rf"= f32\[{u},\d+\][^ ]* custom-call\(f32\[{u},\d+\][^ ]* [^ ]+ f32\[{u},{u}\]")
        if KERNEL in e[0]]
    if not ev:
        return None
    ops, nbytes = ctx.flops.mix_cost(u, ctx.counters["params_per_user"])
    seconds = sum(e - s for _, s, e in ev) * 1e-9
    share, _ = ctx.flops.roofline_share(len(ev) * ops, len(ev) * nbytes, seconds,
                                        ctx.peaks["bf16_flops_per_s"],
                                        ctx.peaks["hbm_bytes_per_s"])
    return share

"""Device time of the top-k compression per round (device trace): XLA's
top-k (a sort of float magnitudes with their indices) and the Pallas mask
kernels (one per leaf: a (users, L) delta in, message and residual out),
over the rounds of the traced window."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr = ctx.tr
    rounds = len(tr.spans("bench.round")) if tr else 0
    if not rounds:
        return None
    u = ctx.counters["users"]
    sort = tr.ops_matching(rf"\) sort\(f32\[{u},\d+\]")
    mask = [e for e in tr.ops_matching(
        rf"= \(f32\[{u},(\d+)\][^ ]* f32\[{u},\1\][^ ]* custom-call\(f32\[{u},\1\]")
        if KERNEL in e[0]]
    if not sort and not mask:
        return None
    return 1e3 * sum(e - s for _, s, e in sort + mask) * 1e-9 / rounds

"""The whole round's share of the chip's peak: the CNN's forward plus
backward operations per sample (``flops.cnn_train_flops``, counted from
the shapes) times the samples per second of the traced window's rounds,
over chips times the bf16 peak (device trace for the window, host spans
for the rounds)."""


def read(ctx):
    tr = ctx.tr
    if tr is None or ctx.peaks is None or tr.window_s <= 0:
        return None
    rounds = len(tr.spans("bench.round"))
    if not rounds:
        return None
    samples_per_s = rounds * ctx.counters["samples_per_round"] / tr.window_s
    c = ctx.counters
    flops = ctx.flops.cnn_train_flops(c["image"], c["classes"], c["channels"], c["hidden"])
    peak = len(ctx.devices) * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops * samples_per_s / peak

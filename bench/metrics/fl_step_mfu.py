"""The whole round's share of the chip's peak: the runner's operations per
training sample (the ``train_flops_per_sample`` counter, counted from the
shapes; for the CNN ``flops.cnn_train_flops``, forward plus backward) times
the samples per second of the traced window's rounds, over chips times the
bf16 peak (device trace for the window, host spans for the rounds)."""


def read(ctx):
    tr = ctx.tr
    if tr is None or ctx.peaks is None or tr.window_s <= 0:
        return None
    rounds = len(tr.spans("bench.round"))
    if not rounds:
        return None
    c = ctx.counters
    samples_per_s = rounds * c["samples_per_round"] / tr.window_s
    peak = len(ctx.devices) * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * c["train_flops_per_sample"] * samples_per_s / peak

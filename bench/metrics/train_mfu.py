"""Model FLOPs of a round (counted from the batch shapes and live rows by
``bench.flops.train_round``) over the round's wall time and the chip's
bf16 peak, in percent."""


def read(run):
    f = getattr(run, "flops_per_round", None)
    if not f or not run.rounds:
        return None
    return f * run.rounds / run.window_s / run.peaks["bf16_flops_per_s"] * 100

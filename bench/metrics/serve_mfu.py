"""Model FLOPs of the rows served in the window (``bench.flops.forward_row``
per row and route) over the window's wall time and the chip's bf16 peak,
in percent."""


def read(run):
    if not run.flops:
        return None
    return run.flops / run.window_s / run.peaks["bf16_flops_per_s"] * 100

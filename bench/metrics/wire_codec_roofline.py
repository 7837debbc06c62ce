"""The wire-codec kernel's share of its roofline, in percent: the least
time its calls could take at the chip's HBM bandwidth over the kernel's
device time in the traced window.

The kernel is the Pallas custom call inside the codec's jitted
``_roundtrip`` (``OP`` below, over ``bench.traces.op_label`` names), in
whatever cell runs it. Each call's bytes come from its own output shape
(rows, n) by ``bench.flops.wire_codec_bytes``. The kernel does a few
element-wise operations per value, so bandwidth and not arithmetic bounds
it."""
import re

from bench.flops import wire_codec_bytes
from bench.traces import op_shape

OP = re.compile(r":_roundtrip(\.\d+)? custom-call ")


def read(run):
    ops = getattr(run, "ops", None)
    if not ops:
        return None
    secs, nbytes = 0, 0
    for evs in ops.values():
        for name, s, e in evs:
            if OP.search(name):
                _, dims = op_shape(name)
                secs += (e - s) / 1e9
                nbytes += wire_codec_bytes(dims[0], dims[-1])
    if not secs:
        return None
    return nbytes / run.peaks["hbm_bytes_per_s"] / secs * 100

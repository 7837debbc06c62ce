"""The trace reduction on a synthetic trace."""
import types

import pytest

from bench import common, flops, traces

MS = 1_000_000


def test_busy_idle_kernel_and_gaps():
    ops = {"/device:TPU:0": [("fusion.1", 10 * MS, 20 * MS),
                             ("fusion.1", 15 * MS, 25 * MS),  # overlaps
                             ("wire_codec", 40 * MS, 45 * MS),
                             ("outside", 200 * MS, 210 * MS)]}
    spans = [(traces.WINDOW_SPAN, 0, 100 * MS),
             ("FederatedBatcher.build", 0, 12 * MS),
             ("bench.round_fn", 30 * MS, 60 * MS)]
    s = traces.summarize(ops, spans)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.020)  # 10-25 and 40-45
    clipped, _, _ = traces.window(ops, spans)
    kernel = [e - b for n, b, e in clipped["/device:TPU:0"] if "wire" in n]
    assert sum(kernel) / 1e9 == pytest.approx(0.005)
    gaps = dict(s["idle_gaps"])
    assert gaps["FederatedBatcher.build"] == pytest.approx(0.010)  # 0-10
    assert gaps["bench.round_fn"] == pytest.approx(0.015)  # 25-40
    assert gaps["(none)"] == pytest.approx(0.055)  # 45-100
    assert dict(s["device_ops"])["fusion.1"] == pytest.approx(0.020)


def test_busy_averages_over_devices_and_needs_window():
    ops = {"/device:TPU:0": [("a", 0, 10 * MS)],
           "/device:TPU:1": [("a", 0, 30 * MS)]}
    s = traces.summarize(ops, [(traces.WINDOW_SPAN, 0, 100 * MS)])
    assert s["busy_s"] == pytest.approx(0.020)
    with pytest.raises(ValueError):
        traces.summarize(ops, [])


@pytest.mark.parametrize("hlo,label", [
    ("%fusion.5 = f32[1024,16,256]{2,0,1:T(8,128)S(1)} fusion(f32[16,256]{1,0} %a)",
     "jit_f:fusion.5 fusion f32[1024,16,256]"),
    ("%sort.5 = (f32[64,256]{1,0:T(8,128)S(1)}, s32[64,256]{1,0:T(8,128)}) "
     "sort(f32[64,256]{1,0} %a)", "jit_f:sort.5 sort f32[64,256]"),
    ("%_roundtrip.4 = f32[64,25]{1,0:T(8,128)S(1)} custom-call(f32[64,25] %a)",
     "jit_f:_roundtrip.4 custom-call f32[64,25]")])
def test_op_labels(hlo, label):
    assert traces.op_label("jit_f", hlo) == label
    dtype, dims = traces.op_shape(label)
    assert dtype == "f32" and dims == tuple(
        int(x) for x in label.rsplit("[", 1)[1][:-1].split(","))


def test_window_clips_ops_and_spans():
    ops = {"/device:TPU:0": [("a", 5 * MS, 15 * MS), ("b", 120 * MS, 130 * MS)]}
    spans = [(traces.WINDOW_SPAN, 10 * MS, 100 * MS),
             ("bench.round_fn", 0, 20 * MS)]
    clipped, host, (lo, hi) = traces.window(ops, spans)
    assert clipped["/device:TPU:0"] == [("a", 10 * MS, 15 * MS)]
    assert host == [("bench.round_fn", 10 * MS, 20 * MS)]
    assert (lo, hi) == (10 * MS, 100 * MS)


def test_kernel_roofline_reads_calls_from_their_shapes():
    """Two codec calls of 1 us each at (64, 256) and (8, 25): their bytes
    at the chip's bandwidth over their summed device time."""
    us = 1000
    ops = {"/device:TPU:0": [
        ("jit_fn:_roundtrip.4 custom-call f32[64,256]", 0, us),
        ("jit_fn:_roundtrip custom-call f32[8,25]", 2 * us, 3 * us),
        ("jit_fn:fusion.1 fusion f32[64,256]", 3 * us, 9 * us)]}
    run = types.SimpleNamespace(ops=ops, peaks={"hbm_bytes_per_s": 819e9})
    got = common.reader("wire_codec_roofline")(run)
    want = (flops.wire_codec_bytes(64, 256) + flops.wire_codec_bytes(8, 25)) \
        / 819e9 / 2e-6 * 100
    assert got == pytest.approx(want)
    assert common.reader("wire_codec_roofline")(
        types.SimpleNamespace(ops={"/device:TPU:0": ops["/device:TPU:0"][2:]},
                              peaks={})) is None

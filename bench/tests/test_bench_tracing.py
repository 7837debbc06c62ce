"""The readers of the program's own spans, on synthetic window events."""
import types

import pytest

from bench import common, traces

MS = 1_000_000
DEV = "/device:TPU:0"


def _run(ops, spans, window_ms=100, rounds=2):
    busy = sum(e - s for s, e in traces._union(
        [(s, e) for _, s, e in ops.get(DEV, [])]))
    return types.SimpleNamespace(
        ops=ops, spans=spans, rounds=rounds,
        trace={"window_s": window_ms / 1e3, "busy_s": busy / 1e9,
               "n_ops": sum(len(v) for v in ops.values())})


@pytest.mark.parametrize("waits,want", [
    ([(50, 75)], 25.0),  # half the 50 ms gap: half of train_idle_pct
    ([(40, 75)], 25.0),  # the part over a busy op does not count
    ([(50, 60), (55, 75), (90, 100)], 35.0),  # overlapping spans count once
    ([(0, 100)], 50.0),  # a wait over the whole window: all the idle time
])
def test_idle_wait_is_the_overlap_of_idle_and_wait(waits, want):
    ops = {DEV: [("fusion.1", 0, 30 * MS), ("fusion.2", 20 * MS, 50 * MS)],
           "/device:TPU:1": [("fusion.1", 50 * MS, 100 * MS)]}
    spans = [("FederatedBatcher.wait", s * MS, e * MS) for s, e in waits]
    spans.append(("FederatedBatcher.build", 0, 100 * MS))
    run = _run(ops, spans)
    assert common.reader("train_idle_wait_pct")(run) == pytest.approx(want)
    assert common.reader("train_idle_pct")(run) == pytest.approx(50.0)


def test_idle_wait_reads_nothing_without_wait_spans_or_ops():
    ops = {DEV: [("fusion.1", 0, 30 * MS)]}
    read = common.reader("train_idle_wait_pct")
    assert read(_run(ops, [("FederatedBatcher.build", 0, 100 * MS)])) is None
    assert read(_run({}, [("FederatedBatcher.wait", 0, 100 * MS)])) is None


def test_put_is_the_union_of_put_spans_per_round():
    """A span placed around ``put`` from outside and the program's own
    span inside it count once."""
    spans = [("FederatedBatcher.put", 0, 10 * MS),
             ("FederatedBatcher.put", 1 * MS, 9 * MS),
             ("FederatedBatcher.put", 50 * MS, 54 * MS),
             ("FederatedBatcher.wait", 10 * MS, 50 * MS)]
    read = common.reader("train_put_ms")
    assert read(_run({}, spans)) == pytest.approx(7.0)
    assert read(_run({}, spans[3:])) is None
    assert read(_run({}, spans, rounds=0)) is None

"""The open-loop generator: deterministic per seed, the same work for
every seed, and latency timed from each request's due time."""
import time

import numpy as np
import pytest

from bench import datastore, serve_cell, traffic_gen
from bench.tests.conftest import tiny

TRAFFIC = {"rate_per_s": 400.0, "rows": {"min": 1, "max": 64},
           "route_mix": {"multimodal": 0.2, "unimodal_A": 0.1,
                         "unimodal_B": 0.1, "vfl_fallback": 0.6}}


def test_schedule_is_deterministic_per_seed():
    a = traffic_gen.schedule(TRAFFIC, 2**31 + 11, 2.0, 128)
    b = traffic_gen.schedule(TRAFFIC, 2**31 + 11, 2.0, 128)
    c = traffic_gen.schedule(TRAFFIC, 5, 2.0, 128)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["rows"], c["rows"])


def test_every_seed_gets_the_same_work():
    a = traffic_gen.schedule(TRAFFIC, 1, 2.0, 128)
    c = traffic_gen.schedule(TRAFFIC, 99, 2.0, 128)
    assert len(a["rows"]) == 800
    assert np.array_equal(np.sort(a["rows"]), np.sort(c["rows"]))
    assert np.array_equal(np.bincount(a["route"]), [160, 80, 80, 480])
    assert np.isclose(a["due"][-1], c["due"][-1], rtol=1e-12, atol=0)
    assert a["rows"].min() >= 1 and a["rows"].max() <= 64
    assert np.all(a["offset"] + a["rows"] <= 128)
    picks = traffic_gen.check_sample(a, 1, 20)
    assert int(np.argmax(a["rows"])) in picks and len(picks) >= 20


class _SlowEngine:
    """Answers each request a fixed time after it was handed over."""

    def __init__(self, delay):
        self.delay = delay

    def serve_stream(self, requests):
        for i, _ in enumerate(requests):
            time.sleep(self.delay)
            yield type("R", (), {"index": i, "scores": np.zeros((1, 2))})()


def test_latency_runs_from_the_due_time():
    sched = {"due": np.array([0.0, 0.001, 0.002]), "rows": np.array([1, 1, 1]),
             "route": np.array([0, 1, 2]), "offset": np.array([0, 0, 0])}
    pool = {"val_a": np.zeros((4, 2, 2)), "val_b": np.zeros((4, 2, 2))}
    t0, issued, done, results = serve_cell.serve_window(_SlowEngine(0.05),
                                                        pool, sched)
    due = t0 + sched["due"]
    lat = done - due
    # the engine answers serially, so the third request waits for the
    # first two: its latency counts that wait from its due time
    assert lat[0] >= 0.05 and lat[2] >= 0.15 - 0.002
    assert np.all(issued >= due - 1e-6)
    assert all(r is not None for r in results)


def test_store_mix_follows_the_store(cache):
    """A ``"store"`` route mix is the store's share of paired, one-sided
    and split patients (the partitioner's 0.4 / 0.3 / 0.3)."""
    info = tiny("mimic_cxr.serve")
    assert info["traffic"]["route_mix"] == "store"
    with pytest.raises(ValueError):
        traffic_gen.schedule(info["traffic"], 1, 1.0, 128)
    store = datastore.ensure(info["config"], cache)
    mix = datastore.availability(store)
    assert sum(mix.values()) == pytest.approx(1.0)
    assert mix["multimodal"] == pytest.approx(0.4, abs=0.01)
    assert mix["vfl_fallback"] == pytest.approx(0.3, abs=0.01)
    assert mix["unimodal_A"] + mix["unimodal_B"] == pytest.approx(0.3, abs=0.01)
    traffic = serve_cell.resolve_traffic(info["traffic"], store)
    sched = traffic_gen.schedule(dict(traffic, rate_per_s=1000.0), 1, 1.0, 128)
    share = np.bincount(sched["route"], minlength=4) / len(sched["route"])
    want = [mix[r] for r in traffic_gen.ROUTES]
    assert np.allclose(share, want, atol=1e-3)

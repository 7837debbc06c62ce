"""In-program tracing: the host spans and counters of the round loop and
the batcher, and the ``jax.named_scope`` of every round stage.

A profiler session over a store-backed ``train_federated.run`` finds each
span with its round number; the batcher's counters agree with the batches
(bytes read) and with the spans (time waited); each stage that runs names
the ``op_name`` of its ops in the compiled round."""
import argparse
import glob
import os
import re
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.federation_sharded import (
    batch_specs,
    init_round_state,
    make_blendfl_round,
)
from repro.data.pipeline import FederatedBatcher
from repro.data.store import write_store
from repro.launch import train_federated as tf

from test_federated_loader import _ragged_clients, _spec, _val

ROUNDS = 3
SPANS = ("FederatedBatcher.build", "FederatedBatcher.wait",
         "FederatedBatcher.put", "train_federated.dispatch",
         "train_federated.sync")
STAGES = ("unimodal", "vfl", "paired", "scaffold", "forge", "codec_uplink",
          "aggregate", "server_update", "codec_downlink", "scatter")


def _host_spans(log_dir: str) -> list:
    """(name, round stat, start_ns, end_ns) of the ``SPANS`` in a trace."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats).get("round"), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events if e.name in SPANS]
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A 3-round store-backed run under the profiler: (store, spec,
    batcher, spans, log lines with their host times)."""
    tmp = tmp_path_factory.mktemp("tracing")
    spec = _spec()
    rng = np.random.default_rng(11)
    store = write_store(str(tmp / "store"), _ragged_clients(spec, rng),
                        _val(spec, rng))
    batcher = FederatedBatcher.from_store(store, spec, seed=5, prefetch=1)
    state = init_round_state(jax.random.PRNGKey(0), spec)
    args = argparse.Namespace(rounds=ROUNDS, log_every=1, ckpt_dir=None,
                              ckpt_every=0)
    logged = []
    log_dir = str(tmp / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        tf.run(args, spec, batcher, jax.jit(make_blendfl_round(spec)), 0,
               state, log=lambda m: logged.append((m, time.perf_counter())))
    finally:
        jax.profiler.stop_trace()
    return store, spec, batcher, _host_spans(log_dir), logged


def test_every_span_carries_its_round(traced):
    *_, spans, _ = traced
    rounds = {name: sorted(r for n, r, _, _ in spans if n == name)
              for name in SPANS}
    every = list(range(ROUNDS))
    assert rounds["FederatedBatcher.build"] == every
    assert rounds["FederatedBatcher.put"] == every
    assert rounds["train_federated.dispatch"] == every
    assert rounds["train_federated.sync"] == every
    # one wait per round, and the last for the end of the stream
    assert rounds["FederatedBatcher.wait"] == every + [ROUNDS]


def test_read_bytes_are_the_rows_drawn(traced):
    """``read_bytes`` equals the drawn rows times their widths: the live
    rows of the masks for phases 1 and 3, and every drawn fragmented row
    (at most the capacity per client) for phase 2."""
    store, spec, batcher, _, _ = traced
    clients = [store.client(c) for c in range(spec.n_clients)]

    def width(*keys):
        return sum(int(np.prod(clients[0][k].shape[1:]))
                   * clients[0][k].dtype.itemsize for k in keys)

    fresh = FederatedBatcher.from_store(store, spec, seed=5)
    want = 0
    for r in range(ROUNDS):
        b = fresh.build(r)
        want += int(b["partial_ma"].sum()) * width("partial_a", "partial_ya")
        want += int(b["partial_mb"].sum()) * width("partial_b", "partial_yb")
        want += int(b["paired_m"].sum()) * width("paired_a", "paired_b",
                                                 "paired_y")
        for c in clients:
            want += min(len(c["frag_a"]), spec.n_frag) * width(
                "frag_a", "frag_y", "frag_ids_a")
            want += min(len(c["frag_b"]), spec.n_frag) * width(
                "frag_b", "frag_ids_b")
    assert batcher.read_bytes == fresh.read_bytes == want
    assert 0 < batcher.read_seconds <= batcher.build_seconds
    assert batcher.put_seconds > 0 and batcher.rounds_built == ROUNDS


def test_stall_is_the_sum_of_the_wait_spans(traced):
    *_, batcher, spans, _ = traced
    waited = sum(e - s for n, _, s, e in spans
                 if n == "FederatedBatcher.wait") / 1e9
    assert batcher.stall_seconds == pytest.approx(waited, abs=1e-3)


def test_log_line_gives_each_rounds_own_time(traced):
    """From round 2 on, each round's figure is the time since the previous
    round's line, not an average since before round 1's compile."""
    *_, logged = traced
    got = [float(re.search(r"\(([0-9.]+)s this round\)", m).group(1))
           for m, _ in logged]
    assert len(got) == ROUNDS
    for r in range(1, ROUNDS):
        assert got[r] == pytest.approx(logged[r][1] - logged[r - 1][1],
                                       abs=0.02)


@pytest.mark.parametrize("kw,running", [
    ({}, {"unimodal", "vfl", "paired", "aggregate", "scatter"}),
    ({"codec": "int8_topk"}, {"unimodal", "vfl", "paired", "codec_uplink",
                              "aggregate", "codec_downlink", "scatter"}),
    ({"strategy": "scaffold"}, {"unimodal", "vfl", "paired", "scaffold",
                                "aggregate", "scatter"}),
    ({"server_opt": "adam", "attacks": True},
     {"unimodal", "vfl", "paired", "forge", "aggregate", "server_update",
      "scatter"}),
])
def test_each_running_stage_names_its_ops(kw, running):
    """The compiled round's ``op_name`` metadata holds the scope of every
    stage that runs and of no other; BlendAvg scores and blends inside
    ``aggregate``."""
    spec = _spec(**kw)
    state = jax.eval_shape(lambda: init_round_state(jax.random.PRNGKey(0),
                                                    spec))
    hlo = jax.jit(make_blendfl_round(spec)).lower(
        state, batch_specs(spec, ragged=True)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = {n.split("/")[1] for n in names if n.startswith("jit(round_fn)/")}
    assert scopes & set(STAGES) == running
    scored = spec.strategy == "blendavg"
    assert any("/aggregate/score/" in n for n in names) == scored
    assert any("/aggregate/blend/" in n for n in names) == scored

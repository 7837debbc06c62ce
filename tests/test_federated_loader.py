"""Federated batch loader + round-state checkpointing.

Covers the ragged-client data subsystem (``FederatedBatcher``): stateless
per-round determinism, static shapes with real 0/1 masks, id-based VFL
alignment, zero-row-modality exclusion semantics (the engine's
``_where_clients`` contract), prefetch equivalence, recycled slabs —
and the full round-state save/restore path: a federation checkpointed
mid-run and resumed must produce bit-identical round metrics to an
uninterrupted run (full participation and K-of-C sampled/async)."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.core.engine import make_phase_fns
from repro.core.federation_sharded import (
    ShardedFedSpec,
    batch_specs,
    init_round_state,
    make_blendfl_round,
)
from repro.data.pipeline import FederatedBatcher


def _ragged_clients(spec, rng, zero_b_client=None, n_rows=None):
    """C ragged synthetic client datasets with disjoint frag id spaces
    split so every a-side id also exists at some b-side client."""
    out = []
    next_id = 0
    for c in range(spec.n_clients):
        n = {k: int(rng.integers(1, cap + 4)) for k, cap in
             (("pa", spec.n_partial), ("pb", spec.n_partial),
              ("fr", spec.n_frag), ("pr", spec.n_paired))}
        if n_rows:
            n.update(n_rows.get(c, {}))
        ids = np.arange(next_id, next_id + n["fr"], dtype=np.int64)
        next_id += n["fr"]
        ds = {
            "partial_a": rng.normal(0, 1, (n["pa"], spec.seq_a, spec.feat_a)).astype(np.float32),
            "partial_ya": (rng.random((n["pa"], spec.out_dim)) < 0.3).astype(np.float32),
            "partial_b": rng.normal(0, 1, (n["pb"], spec.seq_b, spec.feat_b)).astype(np.float32),
            "partial_yb": (rng.random((n["pb"], spec.out_dim)) < 0.3).astype(np.float32),
            "frag_a": rng.normal(0, 1, (n["fr"], spec.seq_a, spec.feat_a)).astype(np.float32),
            "frag_y": (rng.random((n["fr"], spec.out_dim)) < 0.3).astype(np.float32),
            "frag_ids_a": ids,
            "paired_a": rng.normal(0, 1, (n["pr"], spec.seq_a, spec.feat_a)).astype(np.float32),
            "paired_b": rng.normal(0, 1, (n["pr"], spec.seq_b, spec.feat_b)).astype(np.float32),
            "paired_y": (rng.random((n["pr"], spec.out_dim)) < 0.3).astype(np.float32),
        }
        if zero_b_client == c:
            ds["partial_b"] = np.zeros((0, spec.seq_b, spec.feat_b), np.float32)
            ds["partial_yb"] = np.zeros((0, spec.out_dim), np.float32)
        out.append(ds)
    # b-sides of the fragmented rows live at the NEXT client (ragged VFL)
    for c, ds in enumerate(out):
        src = out[(c + 1) % spec.n_clients]
        na = len(src["frag_ids_a"])
        ds["frag_b"] = rng.normal(0, 1, (na, spec.seq_b, spec.feat_b)).astype(np.float32)
        ds["frag_ids_b"] = src["frag_ids_a"].copy()
    return out


def _val(spec, rng):
    return {"val_a": rng.normal(0, 1, (spec.n_val, spec.seq_a, spec.feat_a)).astype(np.float32),
            "val_b": rng.normal(0, 1, (spec.n_val, spec.seq_b, spec.feat_b)).astype(np.float32),
            "val_y": (rng.random((spec.n_val, spec.out_dim)) < 0.3).astype(np.float32)}


def _spec(**kw):
    base = dict(n_clients=4, d_hidden=16, n_layers=1, seq_a=4, feat_a=3,
                seq_b=4, feat_b=3, out_dim=2, n_partial=8, n_frag=8,
                n_paired=8, n_val=16, lr=5e-2, optimizer="adamw")
    base.update(kw)
    return ShardedFedSpec(**base)


@pytest.fixture(scope="module")
def loader():
    spec = _spec()
    rng = np.random.default_rng(0)
    clients = _ragged_clients(spec, rng)
    return spec, FederatedBatcher(clients, spec, _val(spec, rng), seed=3)


# ------------------------------------------------------------ batch layout --

def test_batch_matches_specs_with_masks(loader):
    spec, b = loader
    batch = b.build(0)
    want = b.batch_specs()  # the loader's own contract accessor …
    # … which must agree with the sharded round's ragged spec set
    assert want == batch_specs(spec, ragged=True)
    for k, sd in want.items():
        if k.startswith("val_"):
            continue  # val rides in via put(), not build()
        assert k in batch, f"missing batch key {k}"
        assert batch[k].shape == sd.shape, k
        assert batch[k].dtype == sd.dtype, k
    assert set(batch) == {k for k in want if not k.startswith("val_")}
    dev = b.put(batch)
    for k in ("val_a", "val_b", "val_y"):
        assert dev[k].shape == want[k].shape
    # masks are genuinely ragged 0/1 (not the all-ones uniform layout)
    for mk in ("partial_ma", "partial_mb", "paired_m"):
        m = batch[mk]
        assert set(np.unique(m)) <= {0.0, 1.0}
        assert 0 < m.sum() < m.size
        # live rows are packed at the front of each client's slab
        assert (np.diff(m, axis=1) <= 0).all()


def test_builds_are_deterministic_per_round(loader):
    _, b = loader
    b1, b2 = b.build(5), b.build(5)
    for k in b1:
        np.testing.assert_array_equal(b1[k], np.asarray(b2[k]), err_msg=k)
    b3 = b.build(6)
    assert any(not np.array_equal(b1[k], b3[k]) for k in b1), \
        "different rounds must draw different row subsets"


def test_prefetch_stream_matches_sync_stream(loader):
    _, b = loader
    sync = {r: batch for r, batch in b.rounds(0, 4, prefetch=0)}
    pref = {r: batch for r, batch in b.rounds(0, 4, prefetch=2)}
    assert sorted(sync) == sorted(pref) == [0, 1, 2, 3]
    for r in sync:
        for k in sync[r]:
            np.testing.assert_array_equal(np.asarray(sync[r][k]),
                                          np.asarray(pref[r][k]), err_msg=k)


# ------------------------------------------------------ recycled slabs --

def _shrinking_batchers(tmp_path, source: str, policy: str = "uniform"):
    """Two batchers over one ragged federation (6 clients, 3 sampled a
    round, so a slot's drawn count changes from round to round; one
    client holds no b-side partial rows, another no fragmented or paired
    rows): (spec, under test, reference)."""
    spec = _spec(n_clients=6, n_sampled=3, policy=policy)
    rng = np.random.default_rng(21)
    clients = _ragged_clients(spec, rng, zero_b_client=2,
                              n_rows={4: {"fr": 0, "pr": 0}})
    val = _val(spec, rng)
    if source == "store":
        from repro.data.store import write_store

        store = write_store(str(tmp_path / "store"), clients, val)
        return spec, *(FederatedBatcher.from_store(store, spec, seed=9)
                       for _ in range(2))
    return spec, *(FederatedBatcher(clients, spec, val, seed=9)
                   for _ in range(2))


def _sched(n: int, r: int) -> dict:
    """Telemetry before round ``r``: the stalest clients rotate."""
    return {"last_round": np.roll(np.arange(n, dtype=np.int64) - n, r),
            "omega_ema": np.linspace(0.0, 1.0, n),
            "part_count": np.zeros(n, np.int64)}


def _assert_batch_equal(got: dict, want: dict, r: int) -> None:
    assert set(got) == set(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype, k
        np.testing.assert_array_equal(g, w, err_msg=f"round {r} key {k}")


@pytest.mark.parametrize("source", ["memory", "store"])
@pytest.mark.parametrize("prefetch,policy", [
    (0, "uniform"), (1, "uniform"), (2, "uniform"), (1, "staleness")])
def test_recycled_slab_stream_is_bit_identical(tmp_path, monkeypatch,
                                               source, prefetch, policy):
    """Over ``prefetch + 6`` rounds the stream of ``rounds()``, which
    refills recycled slabs, equals ``put(build(r))`` over fresh arrays bit
    for bit, pad zeros included, on every path: prefetched, depth 0, and
    the synchronous path of a state-reading policy."""
    from repro.data import pipeline

    # the CPU backend may put numpy zero-copy, which keeps a set out of
    # the pool; without that check every set recycles, as on a chip, and
    # each batch is read before the stream resumes
    monkeypatch.setattr(pipeline, "_aliases", lambda arr, host: False)
    spec, b, ref = _shrinking_batchers(tmp_path, source, policy)
    n = prefetch + 6
    scheds = [_sched(spec.n_clients, r) if policy != "uniform" else None
              for r in range(n)]
    told = iter(scheds)  # the synchronous path asks once per round, in order
    live = []
    for r, dev in b.rounds(0, n, prefetch=prefetch,
                           telemetry_fn=lambda: next(told)):
        _assert_batch_equal(dev, ref.put(ref.build(r, scheds[r]), r), r)
        live.append(np.stack([np.asarray(dev[mk]).sum(1) for mk in
                              ("partial_ma", "partial_mb", "paired_m")]))
    assert b.slab_allocs + b.slab_reuses == n == b.rounds_built
    assert b.slab_allocs <= prefetch + 2 and b.slab_reuses > 0
    assert ref.slab_reuses == 0  # build() alone always allocates
    # a slot drew fewer rows than in the round before: its pad rows had
    # to be zeroed again
    assert any((live[r + 1] < live[r]).any() for r in range(n - 1))


@pytest.mark.parametrize("prefetch", [0, 2])
def test_kept_device_batches_are_never_rewritten(tmp_path, prefetch):
    """Every device batch ``rounds()`` yielded, kept to the end of the
    stream, still equals a fresh build of its round: recycling never
    writes into memory a kept batch lives in."""
    _, b, ref = _shrinking_batchers(tmp_path, "memory")
    kept = dict(b.rounds(0, prefetch + 6, prefetch=prefetch))
    for r, dev in kept.items():
        _assert_batch_equal(dev, ref.put(ref.build(r), r), r)


def test_alias_check_sees_zero_copy_puts():
    """``_aliases`` is true exactly when a write to the host array shows
    in the device array."""
    from repro.data.pipeline import _aliases

    for rows in (1, 7, 256):
        host = np.zeros((rows, 1024), np.float32)
        dev = jax.device_put(host)
        dev.block_until_ready()
        host[0, 0] = 1.0
        shared = bool(np.asarray(dev)[0, 0] == 1.0)
        assert _aliases(dev, host) == shared
        assert not _aliases(dev + 0.0, host)


def test_vfl_alignment_pairs_matching_ids(loader):
    spec, b = loader
    batch = b.build(1)
    nf = spec.n_frag
    w = batch["frag_w"]
    assert w.sum() > 0, "some aligned rows must survive"
    # reconstruct the drawn id layout: weight-1 rows must pair a/b sides
    # of the SAME global sample; padded rows carry no label
    assert set(np.unique(w)) <= {0.0, 1.0}
    fy = batch["frag_y"].reshape(spec.k_round * nf, -1)
    assert (fy[w == 0] == 0).all()
    assert batch["frag_part_a"].any() and batch["frag_part_b"].any()
    assert batch["perm_b"].max() < spec.k_round * nf


def test_mismatched_client_arrays_raise_at_init(loader):
    spec, _ = loader
    rng = np.random.default_rng(2)
    clients = _ragged_clients(spec, rng)
    clients[1]["partial_ya"] = clients[1]["partial_ya"][:-1]  # ragged vs x
    with pytest.raises(ValueError, match="partial_a"):
        FederatedBatcher(clients, spec, _val(spec, rng))


def test_prefetch_worker_error_propagates(loader, monkeypatch):
    """A build() failure on the prefetch worker must raise in the
    consumer, not hang it forever on the queue."""
    spec, _ = loader
    rng = np.random.default_rng(4)
    b = FederatedBatcher(_ragged_clients(spec, rng), spec, _val(spec, rng))
    monkeypatch.setattr(b, "build",
                        lambda r: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):
        for _ in b.rounds(0, 2, prefetch=1):
            pass


def test_zero_row_modality_excluded_from_update(loader):
    """A client with a zero-row modality must keep that modality's params
    AND optimizer moments bit-identical through the phase update — the
    engine's ``_where_clients`` contract, now driven by real loader masks
    instead of synthetic ones."""
    spec = _spec()
    rng = np.random.default_rng(1)
    clients = _ragged_clients(spec, rng, zero_b_client=2)
    b = FederatedBatcher(clients, spec, _val(spec, rng), seed=0)
    batch = b.build(0)
    assert batch["partial_mb"][2].sum() == 0  # zero-row modality -> empty mask

    fns = make_phase_fns(spec.engine_cfg)
    state = init_round_state(jax.random.PRNGKey(0), spec)
    p1 = {"xa": jnp.asarray(batch["partial_a"]), "ya": jnp.asarray(batch["partial_ya"]),
          "ma": jnp.asarray(batch["partial_ma"]),
          "xb": jnp.asarray(batch["partial_b"]), "yb": jnp.asarray(batch["partial_yb"]),
          "mb": jnp.asarray(batch["partial_mb"])}
    models, opt, info = fns.unimodal_step(state["models"], state["opt"], p1)
    assert int(info["n_b"][2]) == 0
    for grp in ("f_B", "g_B"):
        for new, old in zip(jax.tree.leaves(models[grp]),
                            jax.tree.leaves(state["models"][grp])):
            np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(old[2]))
            # clients WITH rows did move
            assert not np.array_equal(np.asarray(new[0]), np.asarray(old[0]))
        for new, old in zip(jax.tree.leaves(opt["mu"][grp]),
                            jax.tree.leaves(state["opt"]["mu"][grp])):
            np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(old[2]))


def test_zero_live_vfl_rows_skip_server_head_update(loader):
    """An all-zero ``frag_w`` round (no a-row's PSI partner drawn) has
    exactly-zero VFL grads — the server head's params, moments, and
    schedule step must stay untouched, like every empty-batch client."""
    spec, b = loader
    batch = b.build(0)
    fns = make_phase_fns(spec.engine_cfg)
    state = init_round_state(jax.random.PRNGKey(0), spec)
    K = spec.k_round
    p2 = {"xa": jnp.asarray(batch["frag_a"]), "xb": jnp.asarray(batch["frag_b"]),
          "gather_a": jnp.arange(K * spec.n_frag, dtype=jnp.int32),
          "gather_b": jnp.asarray(batch["perm_b"]),
          "y": jnp.asarray(batch["frag_y"].reshape(K * spec.n_frag, -1)),
          "w": jnp.zeros(K * spec.n_frag, jnp.float32),
          "part_a": jnp.zeros(K, bool), "part_b": jnp.zeros(K, bool)}
    models, gmv, opt, srv, loss = fns.vfl_step(
        state["models"], state["server_gmv"], state["opt"], state["srv_opt"], p2)
    assert float(loss) == 0.0
    for n, o in zip(jax.tree.leaves((gmv, srv)),
                    jax.tree.leaves((state["server_gmv"], state["srv_opt"]))):
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))
    assert int(srv["step"]) == 0
    for n, o in zip(jax.tree.leaves(models), jax.tree.leaves(state["models"])):
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))


def test_ragged_round_runs_and_improves(loader):
    spec, b = loader
    state = init_round_state(jax.random.PRNGKey(0), spec)
    rf = jax.jit(make_blendfl_round(spec))
    losses = []
    for r, batch in b.rounds(0, 3):
        state, m = rf(state, batch)
        losses.append(float(m["loss_uni"]) + float(m["loss_paired"]))
        assert np.isfinite(losses[-1])
    assert int(rf._cache_size()) == 1  # masks/ids are data, not shape


# ------------------------------------------- round-state resume parity -----


def _loader_args(**kw):
    base = dict(task="smnist", clients=4, n_sampled=0, rounds=4, n_train=384,
                n_val=64, rows_cap=16, d_hidden=16, n_layers=1, lr=1e-2,
                optimizer="adamw", dirichlet_alpha=None, seed=0, data_seed=0,
                prefetch=1, ckpt_dir=None, ckpt_every=2, log_every=0)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.mark.slow
def test_resume_parity_full_participation(tmp_path):
    from repro.launch.train_federated import selftest_resume

    selftest_resume(_loader_args())


@pytest.mark.slow
def test_resume_parity_sampled_async(tmp_path):
    from repro.launch.train_federated import selftest_resume

    selftest_resume(_loader_args(clients=6, n_sampled=3))


def test_round_state_checkpoint_bit_exact(tmp_path, loader):
    """The full ``init_round_state`` pytree — stacked models, AdamW
    moments, srv_opt, last_round, round — survives save/restore
    bit-for-bit, including the int32 bookkeeping leaves."""
    spec, b = loader
    state = init_round_state(jax.random.PRNGKey(0), spec)
    rf = jax.jit(make_blendfl_round(spec))
    for _, batch in b.rounds(0, 2):
        state, _ = rf(state, batch)
    save_checkpoint(str(tmp_path), 2, state, {"round": 2})
    target = init_round_state(jax.random.PRNGKey(1), spec)
    restored = restore_checkpoint(str(tmp_path), target)
    assert (jax.tree.structure(restored) == jax.tree.structure(state))
    for a, c in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        assert np.asarray(a).dtype == np.asarray(c).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert int(restored["round"]) == 2
    assert restored["round"].dtype == np.int32

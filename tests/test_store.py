"""Out-of-core client store + docs tooling.

Covers ``repro.data.store``: bit-exact shard round-trips (including
zero-row modalities), the manifest-as-index contract (no file IO for row
counts), ``FederatedBatcher.from_store`` batch streams bit-identical to
the in-memory loader, shard maps held open within the descriptor
budget, the ``rows_for_clients`` multi-host seam, the
checkpoint store-fingerprint guard, store-backed resume parity, and the
``make docs-check`` reference checker."""
import argparse
import gc
import os
import resource
import subprocess
import sys

import numpy as np
import pytest

from repro.data import store as store_mod
from repro.data.pipeline import FederatedBatcher
from repro.data.store import ClientStore, ShardRows, write_store

from test_federated_loader import _ragged_clients, _spec, _val

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_store(tmp_path, spec, rng, **kw):
    clients = _ragged_clients(spec, rng, **kw)
    val = _val(spec, rng)
    store = write_store(str(tmp_path / "store"), clients, val)
    return clients, val, store


# ---------------------------------------------------------- shard round-trip

def test_store_round_trip_bit_exact(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(0)
    clients, val, store = _make_store(tmp_path, spec, rng, zero_b_client=2)
    assert store.n_clients == spec.n_clients
    for cid, src in enumerate(clients):
        view = store.client(cid)
        assert sorted(view.keys()) == sorted(src.keys())
        for key, arr in src.items():
            assert store.rows(cid, key) == len(arr)
            got = view[key][np.arange(len(arr))] if len(arr) else view[key].read()
            np.testing.assert_array_equal(got, arr, err_msg=f"{cid}/{key}")
            assert got.dtype == arr.dtype, f"{cid}/{key}"
    # zero-row modality survives with shape/dtype intact, no mmap needed
    z = store.client(2)["partial_b"]
    assert len(z) == 0 and z.read().shape[1:] == (spec.seq_b, spec.feat_b)
    for k, v in val.items():
        np.testing.assert_array_equal(store.val()[k], v, err_msg=k)


def test_store_subset_reads_only_selected_rows(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(1)
    clients, _, store = _make_store(tmp_path, spec, rng)
    n = len(clients[1]["partial_a"])
    sel = rng.permutation(n)[: max(1, n // 2)]
    np.testing.assert_array_equal(store.client(1)["partial_a"][sel],
                                  clients[1]["partial_a"][sel])


def test_rows_for_clients_mesh_seam(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(2)
    clients, _, store = _make_store(tmp_path, spec, rng)
    ids = [3, 1]
    sels = [np.arange(min(2, len(clients[i]["frag_a"]))) for i in ids]
    out = store.rows_for_clients(ids, {"frag_a": sels, "frag_ids_a": sels})
    for j, cid in enumerate(ids):
        np.testing.assert_array_equal(out["frag_a"][j],
                                      clients[cid]["frag_a"][sels[j]])
        np.testing.assert_array_equal(out["frag_ids_a"][j],
                                      clients[cid]["frag_ids_a"][sels[j]])
    with pytest.raises(ValueError, match="selections for"):
        store.rows_for_clients([0], {"frag_a": sels})


def test_write_store_refuses_silent_overwrite(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(3)
    clients = _ragged_clients(spec, rng)
    val = _val(spec, rng)
    write_store(str(tmp_path / "s"), clients, val)
    with pytest.raises(FileExistsError):
        write_store(str(tmp_path / "s"), clients, val)
    write_store(str(tmp_path / "s"), clients, val, overwrite=True)  # explicit ok
    assert ClientStore(str(tmp_path / "s")).n_clients == spec.n_clients


def test_store_old_fallback_after_crashed_swap(tmp_path):
    """A crash between an overwrite swap's two renames leaves the
    complete previous store only at <dir>.old — reads must fall back to
    it, and the next import must sweep it."""
    spec = _spec()
    rng = np.random.default_rng(7)
    clients, val, store = _make_store(tmp_path, spec, rng)
    fp = store.fingerprint()
    os.rename(str(tmp_path / "store"), str(tmp_path / "store.old"))
    recovered = ClientStore(str(tmp_path / "store"))
    assert recovered.fingerprint() == fp
    np.testing.assert_array_equal(
        recovered.client(0)["partial_a"].read(), clients[0]["partial_a"])
    write_store(str(tmp_path / "store"), clients, val)
    assert not os.path.exists(str(tmp_path / "store.old"))
    assert ClientStore(str(tmp_path / "store")).fingerprint() == fp


def test_fingerprint_identifies_contents(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(4)
    clients, val, store = _make_store(tmp_path, spec, rng)
    fp = store.fingerprint()
    assert ClientStore(store.store_dir).fingerprint() == fp  # stable reopen
    clients[0]["partial_a"] = clients[0]["partial_a"] + 1.0
    store2 = write_store(str(tmp_path / "other"), clients, val)
    assert store2.fingerprint() != fp  # per-shard sha256 in the manifest


# ------------------------------------------------- from_store batch parity --

@pytest.mark.parametrize("spec_kw", [{}, {"n_clients": 6, "n_sampled": 3}])
def test_from_store_batches_bit_identical(tmp_path, spec_kw):
    spec = _spec(**spec_kw)
    rng = np.random.default_rng(5)
    clients, val, store = _make_store(tmp_path, spec, rng)
    mem = FederatedBatcher(clients, spec, val, seed=7)
    sto = FederatedBatcher.from_store(store, spec, seed=7)
    assert sto.store is store and mem.store is None
    for r in (0, 1, 9):
        bm, bs = mem.build(r), sto.build(r)
        assert set(bm) == set(bs)
        for k in bm:
            np.testing.assert_array_equal(bm[k], bs[k],
                                          err_msg=f"round {r} key {k}")
            assert bm[k].dtype == np.asarray(bs[k]).dtype, k
    if spec.n_sampled:
        assert "sampled" in sto.build(0)
    for k in ("val_a", "val_b", "val_y"):  # store-recorded val rides put()
        np.testing.assert_array_equal(np.asarray(mem._val[k]),
                                      np.asarray(sto._val[k]), err_msg=k)


# ------------------------------------------------- shard maps held open ----

def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def test_shard_maps_open_once_per_shard_read(tmp_path, monkeypatch):
    """Over a stream of rounds a store-backed batcher opens one map per
    distinct shard it read, and a second pass over the same rounds opens
    none."""
    spec = _spec(n_clients=6, n_sampled=3)
    rng = np.random.default_rng(12)
    _, _, store = _make_store(tmp_path, spec, rng, zero_b_client=2)
    read = set()
    real = ShardRows._gather

    def spy(self, fn):
        if self._maps is store.maps:
            read.add(self.path)
        return real(self, fn)

    monkeypatch.setattr(ShardRows, "_gather", spy)
    b = FederatedBatcher.from_store(store, spec, seed=3)
    for _ in b.rounds(0, 6, prefetch=1):
        pass
    assert 0 < b.map_opens == len(read) <= store.maps.n_shards
    assert b.reads_unmapped == 0
    opened = b.map_opens
    for _ in b.rounds(0, 6, prefetch=1):
        pass
    assert b.map_opens == opened


def test_reads_past_the_descriptor_budget_stay_unmapped(tmp_path,
                                                        monkeypatch):
    """With room for two maps the stream is unchanged: the other shards
    are read by opening, gathering and closing, as before maps were
    held."""
    monkeypatch.setattr(store_mod, "_map_budget", lambda need: 2)
    spec = _spec()
    rng = np.random.default_rng(13)
    clients, val, store = _make_store(tmp_path, spec, rng, zero_b_client=1)
    mem = FederatedBatcher(clients, spec, val, seed=4)
    sto = FederatedBatcher.from_store(store, spec, seed=4)
    for r, dev in sto.rounds(0, 5, prefetch=1):
        want = mem.build(r)
        for k in want:
            np.testing.assert_array_equal(np.asarray(dev[k]), want[k],
                                          err_msg=f"round {r} key {k}")
    assert sto.map_opens == 2 and sto.reads_unmapped > 0


def test_map_budget_raises_the_soft_limit_only_as_far_as_needed():
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    low = _open_fds() + store_mod._FD_RESERVE + 10
    need = 50
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (low, hard))
        budget = store_mod._map_budget(need)
        raised = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))
    if hard == resource.RLIM_INFINITY or hard >= low + need:
        assert budget == need
        assert low < raised <= low + need  # toward the need, not the hard
    else:
        assert raised == hard and 0 <= budget < need


def test_take_gathers_into_out_and_close_or_drop_releases_maps(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(14)
    clients, _, store = _make_store(tmp_path, spec, rng)
    rows = store.client(1)["partial_a"]
    src = clients[1]["partial_a"]
    sel = rng.permutation(len(src))[: max(1, len(src) // 2)]
    out = np.full((len(sel) + 2,) + src.shape[1:], np.nan, np.float32)
    rows.take(sel, axis=0, out=out[: len(sel)], mode="clip")
    np.testing.assert_array_equal(out[: len(sel)], src[sel])
    assert np.isnan(out[len(sel):]).all()  # nothing past its block
    assert store.maps.map_opens == 1
    fds = _open_fds()
    store.close()
    assert _open_fds() == fds - 1
    np.testing.assert_array_equal(rows[sel], src[sel])  # maps again
    assert store.maps.map_opens == 2
    del store, rows  # dropping the store releases its maps too
    gc.collect()
    assert _open_fds() == fds - 1


def test_from_store_round_runs(tmp_path):
    import jax

    from repro.core.federation_sharded import init_round_state, make_blendfl_round

    spec = _spec()
    rng = np.random.default_rng(6)
    _, _, store = _make_store(tmp_path, spec, rng)
    b = FederatedBatcher.from_store(store, spec, seed=0)
    state = init_round_state(jax.random.PRNGKey(0), spec)
    rf = jax.jit(make_blendfl_round(spec))
    for _, batch in b.rounds(0, 2):
        state, m = rf(state, batch)
        assert np.isfinite(float(m["loss_uni"]))
    assert int(rf._cache_size()) == 1


# ------------------------------------------- fingerprint-guarded resume -----

def _driver_args(**kw):
    base = dict(task="smnist", clients=4, n_sampled=0, rounds=4, n_train=384,
                n_val=64, rows_cap=16, d_hidden=16, n_layers=1, lr=1e-2,
                optimizer="adamw", dirichlet_alpha=None, seed=0, data_seed=0,
                prefetch=1, ckpt_dir=None, ckpt_every=2, log_every=0,
                store_dir=None, overwrite=False, command=None)
    base.update(kw)
    return argparse.Namespace(**base)


def test_resume_refuses_foreign_store_fingerprint(tmp_path):
    import jax

    from repro.checkpoint import read_metadata, save_checkpoint
    from repro.core.federation_sharded import init_round_state
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train_federated import init_or_restore

    spec = _spec()
    state = init_round_state(jax.random.PRNGKey(0), spec)
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, 2, state, {"round": 2, "store_fingerprint": "a" * 64})
    assert read_metadata(ckpt)["store_fingerprint"] == "a" * 64
    args = _driver_args(ckpt_dir=ckpt)
    mesh = make_host_mesh()
    with pytest.raises(ValueError, match="different client store"):
        init_or_restore(args, spec, mesh, store_fingerprint="b" * 64)
    with pytest.raises(ValueError, match="store-backed run"):
        init_or_restore(args, spec, mesh, store_fingerprint=None)
    # matching fingerprint restores fine
    start, _ = init_or_restore(args, spec, mesh, store_fingerprint="a" * 64)
    assert start == 2


@pytest.mark.slow
def test_resume_parity_store_backed(tmp_path):
    """The bit-exact killed-and-resumed guarantee holds when every batch
    is served from shard files instead of host RAM."""
    from repro.launch.train_federated import import_store, selftest_resume

    args = _driver_args(store_dir=str(tmp_path / "store"))
    import_store(args)
    selftest_resume(args)


# --------------------------------------------------------------- docs-check

def _docs_check(*extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "docs_check.py"),
         *extra], capture_output=True, text=True)


def test_docs_check_passes_on_repo_docs():
    r = _docs_check()
    assert r.returncode == 0, r.stdout + r.stderr


def test_docs_check_flags_broken_refs(tmp_path):
    bad = tmp_path / "bad.md"
    bad.write_text("see `src/repro/nope_missing.py`, `repro.not.a.module`, "
                   "[link](gone.md), and run `make not-a-target`\n")
    r = _docs_check(str(bad))
    assert r.returncode == 1
    for frag in ("nope_missing", "repro.not.a.module", "gone.md",
                 "not-a-target"):
        assert frag in r.stdout, (frag, r.stdout)

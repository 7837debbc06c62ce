"""``repro.core.state`` — the round-state block registry + elastic cohorts.

The registry is the single source of state-block layout for BOTH
drivers, so these tests pin its two contracts directly on a real
sharded round state carrying every optional block (int8_topk codec
residuals, SCAFFOLD control variates, server-Adam moments):

- **Round-trip identity** (property-based, via ``hypothesis``):
  for every registered block and any sampled id set, gathering the K
  rows and scattering them back unchanged reproduces the full state
  bit-exactly — the invariant that makes the drivers' shared
  sample/scatter path a refactor rather than a behavior change.
- **Elastic capacity**: ``grow`` pads to a bucket without touching
  existing rows (new model rows adopt the current globals, moments /
  residuals / variates zero, ``last_round`` -1), shrinking is refused,
  ``retire_clients`` resets exactly the named slots, and a
  smaller-capacity checkpoint migrates into a bigger federation through
  ``train_federated.init_or_restore`` (restore bit-exact, then grow).
- **K > C is a loud error** in both drivers' entry points.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import state as rstate


def _spec(C: int, **kw):
    from repro.core.federation_sharded import ShardedFedSpec

    base = dict(n_clients=C, d_hidden=8, n_layers=2, seq_a=4, feat_a=3,
                seq_b=4, feat_b=3, out_dim=3, kind="multiclass", n_partial=4,
                n_frag=4, n_paired=4, n_val=8, n_sampled=min(2, C),
                codec="int8_topk", strategy="scaffold", server_opt="adam",
                optimizer="adamw")
    base.update(kw)
    return ShardedFedSpec(**base)


@functools.lru_cache(maxsize=None)
def _state(C: int) -> dict:
    """A real sharded round state at capacity C with EVERY optional
    block present (codec + strat, incl. server moments)."""
    from repro.core.federation_sharded import init_round_state

    return init_round_state(jax.random.PRNGKey(0), _spec(C))


def _tree_equal(a, b) -> bool:
    leaves_a, treedef_a = jax.tree.flatten(a)
    leaves_b, treedef_b = jax.tree.flatten(b)
    return treedef_a == treedef_b and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b))


def test_registry_covers_real_state():
    state = _state(4)
    assert set(state) == {b.name for b in rstate.REGISTRY}
    optional = {b.name for b in rstate.REGISTRY if b.optional}
    assert optional == {"codec", "strat"}


@settings(max_examples=20, deadline=None)
@given(c=st.sampled_from([2, 4, 8, 11]), k=st.integers(1, 8),
       seed=st.integers(0, 10**6))
def test_sample_scatter_roundtrip(c, k, seed):
    """scatter(state, sample(state, idx), idx) == state, bit-exact, for
    every registered block, across (C, K) grids and arbitrary id sets."""
    state = _state(c)
    k = min(k, c)
    idx = np.random.default_rng(seed).choice(c, size=k, replace=False)
    sub = rstate.sample(state, idx)
    # the gather really is K rows for stacked blocks
    assert sub["last_round"].shape == (k,)
    assert all(x.shape[0] == k
               for x in jax.tree.leaves(sub["models"]["f_A"]))
    back = rstate.scatter(state, sub, idx)
    assert _tree_equal(back, state)


def test_full_participation_passthrough():
    """idx=None (full participation) samples to the identity and
    scatters wholesale — the no-sampling drivers' path."""
    state = _state(4)
    assert rstate.sample(state, None) is not state  # new dict, same leaves
    assert _tree_equal(rstate.sample(state, None), state)
    assert _tree_equal(rstate.scatter(state, dict(state), None), state)


def test_unregistered_block_raises():
    with pytest.raises(KeyError, match="unregistered round-state block"):
        rstate.sample({"bogus": jnp.zeros((4,))}, np.array([0, 1]))


def test_capacity_for_buckets():
    assert [rstate.capacity_for(n) for n in (1, 7, 8, 9, 16, 17)] == \
        [8, 8, 8, 16, 16, 24]
    with pytest.raises(ValueError, match="must be >= 1"):
        rstate.capacity_for(0)


def test_grow_is_bit_exact_on_existing_rows():
    state = _state(8)
    grown = rstate.grow(state, 16)
    assert rstate.state_capacity(grown) == 16
    # every stacked leaf keeps its first 8 rows bit-exactly; "none"
    # blocks are untouched
    sub = rstate.sample(grown, np.arange(8))
    assert _tree_equal(sub, state)


def test_grow_fills_new_rows_by_block():
    state = _state(8)
    grown = rstate.grow(state, 16)
    new = rstate.sample(grown, np.arange(8, 16))
    # joiners' models adopt the current globals (Algorithm 1 shared init)
    for g in rstate.CLIENT_GROUPS:
        jax.tree.map(
            lambda x, glob: np.testing.assert_array_equal(
                np.asarray(x), np.broadcast_to(np.asarray(glob), x.shape)),
            new["models"][g], state["global_models"][g])
    # moments / residuals / control variates start at zero
    for mk in rstate.OPT_MOMENT_KEYS:
        if mk in new["opt"]:
            assert all(not np.asarray(x).any()
                       for x in jax.tree.leaves(new["opt"][mk]))
    assert all(not np.asarray(x).any()
               for x in jax.tree.leaves(new["codec"]["resid_up"]))
    assert all(not np.asarray(x).any()
               for x in jax.tree.leaves(new["strat"]["c_local"]))
    # async/sched bookkeeping starts like a fresh federation
    assert np.all(np.asarray(new["last_round"]) == -1)
    assert np.all(np.asarray(new["sched"]["last_round"]) == -1)
    assert not np.asarray(new["sched"]["part_count"]).any()
    assert not np.asarray(new["sched"]["omega_ema"]).any()
    # unstacked halves replace nothing: c_global / srv / resid_down and
    # the global blocks are the same values
    assert _tree_equal(grown["strat"]["c_global"], state["strat"]["c_global"])
    assert _tree_equal(grown["codec"]["resid_down"],
                       state["codec"]["resid_down"])
    assert _tree_equal(grown["global_models"], state["global_models"])


def test_grow_same_capacity_is_identity_and_shrink_raises():
    state = _state(8)
    assert rstate.grow(state, 8) is state
    with pytest.raises(ValueError, match="cannot shrink"):
        rstate.grow(state, 4)


def test_retire_clients_resets_only_named_slots():
    state = _state(8)
    retired = rstate.retire_clients(state, [1, 3])
    keep = np.array([0, 2, 4, 5, 6, 7])
    assert _tree_equal(rstate.sample(retired, keep),
                       rstate.sample(state, keep))
    gone = rstate.sample(retired, np.array([1, 3]))
    for g in rstate.CLIENT_GROUPS:
        jax.tree.map(
            lambda x, glob: np.testing.assert_array_equal(
                np.asarray(x), np.broadcast_to(np.asarray(glob), x.shape)),
            gone["models"][g], state["global_models"][g])
    assert np.all(np.asarray(gone["last_round"]) == -1)
    assert all(not np.asarray(x).any()
               for x in jax.tree.leaves(gone["strat"]["c_local"]))


def test_checkpoint_migration_grows_smaller_capacity(tmp_path):
    """A capacity-8 checkpoint resumes into a capacity-16 federation:
    bit-exact restore of the old rows, declared fills for the new ones —
    and shrinking in place is refused with the migration hint."""
    import argparse

    from repro.checkpoint import read_manifest, save_checkpoint
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train_federated import init_or_restore

    state = _state(8)
    ckpt = tmp_path / "ck"
    save_checkpoint(str(ckpt), 3, state, {"round": 3})
    manifest = read_manifest(str(ckpt), 3)
    assert rstate.manifest_capacity(manifest) == 8

    mesh = make_host_mesh()
    args = argparse.Namespace(seed=0, ckpt_dir=str(ckpt))
    start, migrated = init_or_restore(args, _spec(16), mesh)
    assert start == 3
    assert rstate.state_capacity(migrated) == 16
    assert _tree_equal(jax.device_get(migrated),
                       jax.device_get(rstate.grow(state, 16)))
    with pytest.raises(ValueError, match="shrinking a cohort in place"):
        init_or_restore(argparse.Namespace(seed=0, ckpt_dir=str(ckpt)),
                        _spec(4, n_sampled=2), mesh)


def test_manifest_capacity_requires_round_state():
    with pytest.raises(KeyError, match="not a round-state checkpoint"):
        rstate.manifest_capacity({"shapes": {}, "dtypes": {}, "keys": []})


def test_k_greater_than_c_raises_sharded():
    with pytest.raises(ValueError, match="n_sampled=9"):
        _spec(4, n_sampled=9)


def test_k_greater_than_c_raises_in_host():
    from repro.core.federation import FedConfig, Federation

    with pytest.raises(ValueError, match="n_sampled=9"):
        Federation.init(jax.random.PRNGKey(0),
                        FedConfig(n_clients=4, n_sampled=9),
                        None, None, [], None)

"""Substrate tests: optimizers, schedules, metrics, checkpointing, data."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.data.pipeline import Batcher, token_batches
from repro.data.synthetic import generate, make_task, train_val_test
from repro.metrics import auprc, auroc, bootstrap_ci


# -------------------------------------------------------------- optimizers --

def test_adamw_minimizes_quadratic():
    opt = optim.adamw(0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree.map(lambda p: 2 * p, params)
        updates, state = opt.update(grads, state, params)
        params = optim.apply_updates(params, updates)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_adamw_weight_decay_shrinks():
    opt = optim.adamw(0.01, weight_decay=0.5)
    params = {"w": jnp.ones(4)}
    state = opt.init(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    for _ in range(10):
        updates, state = opt.update(zero_g, state, params)
        params = optim.apply_updates(params, updates)
    assert float(params["w"][0]) < 1.0


def test_sgd_momentum():
    opt = optim.sgd(0.1, momentum=0.9)
    params = {"w": jnp.asarray(4.0)}
    state = opt.init(params)
    for _ in range(200):
        updates, state = opt.update({"w": 2 * params["w"]}, state, params)
        params = optim.apply_updates(params, updates)
    assert abs(float(params["w"])) < 5e-2


def test_global_norm_clip():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = optim.global_norm_clip(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
    np.testing.assert_allclose(
        float(jnp.sqrt(jnp.sum(jnp.square(clipped["a"])))), 1.0, rtol=1e-5)


def test_schedules():
    sched = optim.linear_warmup_cosine(1.0, warmup=10, total_steps=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(sched(jnp.asarray(10))), 1.0, rtol=1e-5)
    assert float(sched(jnp.asarray(100))) < 0.2


# ----------------------------------------------------------------- metrics --

def test_auroc_known_values():
    y = np.array([0, 0, 1, 1])
    s = np.array([0.1, 0.4, 0.35, 0.8])
    np.testing.assert_allclose(auroc(y, s), 0.75)  # sklearn's doc example
    assert auroc(np.array([1, 1]), np.array([0.5, 0.6])) != auroc(y, s)  # nan path
    assert np.isnan(auroc(np.array([1, 1]), np.array([0.5, 0.6])))


def test_auroc_perfect_and_random():
    y = np.array([0, 0, 1, 1])
    assert auroc(y, np.array([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert auroc(y, np.array([0.9, 0.8, 0.2, 0.1])) == 0.0
    np.testing.assert_allclose(auroc(y, np.array([0.5, 0.5, 0.5, 0.5])), 0.5)


def test_auprc_known_value():
    y = np.array([0, 0, 1, 1])
    s = np.array([0.1, 0.4, 0.35, 0.8])
    np.testing.assert_allclose(auprc(y, s), 0.8333333, rtol=1e-5)


@given(n=st.integers(10, 200), seed=st.integers(0, 100))
@settings(max_examples=30, deadline=None)
def test_auroc_is_rank_statistic(n, seed):
    """AUROC must be invariant to any monotone transform of the scores."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n)
    s = rng.normal(0, 1, n)
    if y.sum() in (0, n):
        return
    a1 = auroc(y, s)
    a2 = auroc(y, np.tanh(s) * 3 + 7)
    np.testing.assert_allclose(a1, a2, rtol=1e-9)


def test_bootstrap_ci_brackets_point():
    rng = np.random.default_rng(0)
    y = rng.integers(0, 2, 500)
    s = y * 0.5 + rng.normal(0, 0.5, 500)
    point, lo, hi = bootstrap_ci(auroc, y, s, n_boot=100)
    assert lo <= point <= hi
    assert hi - lo < 0.2


# ------------------------------------------------------------- checkpoints --

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.int32), "d": [jnp.zeros(2), jnp.ones(1)]}}
    save_checkpoint(str(tmp_path), 7, tree, {"note": "x"})
    assert latest_step(str(tmp_path)) == 7
    zeros = jax.tree.map(jnp.zeros_like, tree)
    restored = restore_checkpoint(str(tmp_path), zeros)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"a": jnp.zeros(4)})


def test_checkpoint_picks_latest(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros(2)})
    save_checkpoint(str(tmp_path), 12, {"a": jnp.ones(2)})
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones(2))


def test_checkpoint_save_is_atomic(tmp_path):
    """A crash mid-write must never leave a partial ``step_N`` for
    ``latest_step`` to pick up: writes stage in ``step_N.tmp`` and rename
    into place; stale .tmp dirs are invisible to step selection."""
    import os

    # simulate a writer that died mid-write: a .tmp staging dir exists
    crashed = tmp_path / "step_00000009.tmp"
    crashed.mkdir()
    (crashed / "arrays.npz").write_bytes(b"partial garbage")
    assert latest_step(str(tmp_path)) is None  # .tmp is not a checkpoint

    save_checkpoint(str(tmp_path), 3, {"a": jnp.ones(2)})
    assert latest_step(str(tmp_path)) == 3
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path)
                   if d.startswith("step_00000003"))
    # a save of the crashed step sweeps the stale staging dir
    save_checkpoint(str(tmp_path), 9, {"a": jnp.full(2, 5.0)})
    assert not crashed.exists()
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full(2, 5.0))
    # overwriting an existing step replaces it atomically
    save_checkpoint(str(tmp_path), 9, {"a": jnp.full(2, 7.0)})
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)}, step=9)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full(2, 7.0))


def test_checkpoint_recovers_crashed_overwrite_swap(tmp_path):
    """Crash between the overwrite swap's two renames leaves the complete
    previous step as ``step_N.old``; latest_step/restore must still find
    it (read-only fallback — no rename, so readers can't race a live
    writer) instead of silently falling back to an older step."""
    import os

    save_checkpoint(str(tmp_path), 3, {"a": jnp.ones(2)})
    save_checkpoint(str(tmp_path), 9, {"a": jnp.full(2, 9.0)})
    # simulate the crash window: step_9 moved aside, new rename never ran
    os.rename(tmp_path / "step_00000009", tmp_path / "step_00000009.old")
    assert latest_step(str(tmp_path)) == 9  # found via .old, not 3
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full(2, 9.0))
    # the completed step wins over its own leftover .old, which the next
    # save of that step sweeps
    (tmp_path / "step_00000003.old").mkdir()
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)}, step=3)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones(2))
    save_checkpoint(str(tmp_path), 3, {"a": jnp.full(2, 4.0)})
    assert not (tmp_path / "step_00000003.old").exists()
    # re-saving the crashed step itself also sweeps the stale .old
    save_checkpoint(str(tmp_path), 9, {"a": jnp.full(2, 10.0)})
    assert not (tmp_path / "step_00000009.old").exists()
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(2)}, step=9)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full(2, 10.0))


def test_checkpoint_dtype_kind_mismatch_raises(tmp_path):
    """An int leaf restored into a float tree (e.g. ``last_round`` into a
    model leaf) must raise instead of passing a shape-only check."""
    save_checkpoint(str(tmp_path), 1, {"a": jnp.arange(3, dtype=jnp.int32)})
    with pytest.raises(ValueError, match="dtype mismatch"):
        restore_checkpoint(str(tmp_path), {"a": jnp.zeros(3, jnp.float32)})


def test_checkpoint_within_kind_casts_to_target(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": np.arange(3, dtype=np.float64)})
    out = restore_checkpoint(str(tmp_path), {"a": jnp.zeros(3, jnp.float32)})
    assert np.asarray(out["a"]).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(out["a"]), [0.0, 1.0, 2.0])


def test_checkpoint_duplicate_flat_key_raises(tmp_path):
    """Nested {"a": {"b": ...}} collides with a literal "a/b" key in the
    flattened npz namespace — one leaf would silently win."""
    tree = {"a": {"b": jnp.zeros(2)}, "a/b": jnp.ones(2)}
    with pytest.raises(ValueError, match="duplicate"):
        save_checkpoint(str(tmp_path), 1, tree)


def test_train_style_resume_restores_opt_state(tmp_path):
    """Regression for the launch/train.py resume bug: params and
    opt_state checkpoint and restore TOGETHER, so AdamW moments and the
    schedule step survive a resume instead of replaying warmup."""
    from repro import optim

    params = {"w": jnp.ones((2, 2)), "b": jnp.zeros(2)}
    opt = optim.adamw(optim.linear_warmup_cosine(1e-3, warmup=10, total_steps=100))
    opt_state = opt.init(params)
    grads = jax.tree.map(jnp.ones_like, params)
    for _ in range(7):
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
    save_checkpoint(str(tmp_path), 7, {"params": params, "opt_state": opt_state})

    fresh = {"w": jnp.ones((2, 2)), "b": jnp.zeros(2)}
    target = {"params": fresh, "opt_state": opt.init(fresh)}
    restored = restore_checkpoint(str(tmp_path), target)
    assert int(restored["opt_state"]["step"]) == 7  # schedule step survives
    assert restored["opt_state"]["step"].dtype == np.int32
    for a, b in zip(jax.tree.leaves(restored["opt_state"]),
                    jax.tree.leaves(opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------------- data --

def test_synthetic_is_learnable_and_complementary():
    """Modality A and B must each be predictive, and jointly more so —
    the structural property the paper's tables depend on."""
    spec = make_task("mortality")
    tr, va, te = train_val_test(spec, 2000, 10, 500, seed=0)

    # linear probe: least squares on flattened features
    def probe(xtr, xte):
        a = xtr.reshape(len(xtr), -1)
        w = np.linalg.lstsq(np.c_[a, np.ones(len(a))], tr.y[:, 0], rcond=None)[0]
        at = xte.reshape(len(xte), -1)
        return at @ w[:-1] + w[-1]

    flat = lambda d: d.reshape(len(d), -1)
    sa = probe(tr.x_a, te.x_a)
    sb = probe(tr.x_b, te.x_b)
    sj = probe(np.concatenate([flat(tr.x_a), flat(tr.x_b)], 1),
               np.concatenate([flat(te.x_a), flat(te.x_b)], 1))
    a_a, a_b, a_j = (auroc(te.y[:, 0], s) for s in (sa, sb, sj))
    assert a_a > 0.6 and a_b > 0.6
    assert a_j > max(a_a, a_b) - 0.02


def test_splits_are_disjoint():
    spec = make_task("smnist")
    tr, va, te = train_val_test(spec, 100, 50, 50, seed=0)
    assert not (set(tr.ids) & set(va.ids) or set(tr.ids) & set(te.ids)
                or set(va.ids) & set(te.ids))


def test_batcher_covers_all_rows():
    arrays = {"x": np.arange(23), "y": np.arange(23) * 2}
    bt = Batcher(arrays, 5, seed=0)
    seen = np.concatenate([b["x"] for b in bt.epoch()])
    assert sorted(seen.tolist()) == list(range(23))
    bt2 = Batcher(arrays, 5, seed=0, drop_remainder=True)
    seen2 = np.concatenate([b["x"] for b in bt2.epoch()])
    assert len(seen2) == 20


def test_token_batches_shapes():
    for b in token_batches(100, 4, 16, 3):
        assert b["tokens"].shape == (4, 16) and b["labels"].shape == (4, 16)
        assert b["tokens"].max() < 100

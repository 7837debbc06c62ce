"""BlendAvg (Eq. 9-11) unit + property tests."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.blendavg import blend_trees, blendavg, blendavg_weights, fedavg


# ------------------------------------------------------------- unit tests --

def test_weights_discard_nonimproving():
    w = blendavg_weights([0.7, 0.5, 0.9], global_score=0.6)
    assert w[1] == 0.0  # 0.5 <= 0.6 discarded
    assert w[0] > 0 and w[2] > 0
    assert w[2] > w[0]  # bigger improvement -> bigger weight
    np.testing.assert_allclose(w.sum(), 1.0)


def test_weights_all_worse_gives_zero_vector():
    w = blendavg_weights([0.1, 0.2], global_score=0.5)
    assert w.sum() == 0.0


def test_blendavg_keeps_global_when_no_improvement():
    glob = {"w": jnp.ones(8)}
    cands = [{"w": jnp.zeros(8)}, {"w": 2 * jnp.ones(8)}]
    scores = {id(cands[0]): 0.1, id(cands[1]): 0.2}
    blended, info = blendavg(glob, cands, lambda m: scores.get(id(m), 0.9))
    assert info["kept_global"]
    np.testing.assert_array_equal(np.asarray(blended["w"]), np.ones(8))


def test_blendavg_proportional_blend():
    glob = {"w": jnp.zeros(4)}
    cands = [{"w": jnp.ones(4)}, {"w": 3 * jnp.ones(4)}]
    # improvements 0.1 and 0.3 -> weights 0.25 / 0.75 -> blend = 2.5
    ev = {id(glob): 0.5, id(cands[0]): 0.6, id(cands[1]): 0.8}
    blended, info = blendavg(glob, cands, lambda m: ev[id(m)])
    np.testing.assert_allclose(np.asarray(blended["w"]), 2.5 * np.ones(4), rtol=1e-6)
    assert not info["kept_global"]


def test_fedavg_volume_weights():
    cands = [{"w": jnp.ones(4)}, {"w": 5 * jnp.ones(4)}]
    out = fedavg(cands, n_samples=[3, 1])
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0 * np.ones(4), rtol=1e-6)


def test_fedavg_all_zero_samples_raises():
    """Regression: the silent max(tot, 1.0) floor used to blend all-zero
    weights into an all-zero model. Zero total volume is now an explicit
    error (the engine path keeps the previous global model instead)."""
    cands = [{"w": jnp.ones(4)}, {"w": 5 * jnp.ones(4)}]
    with pytest.raises(ValueError, match="zero"):
        fedavg(cands, n_samples=[0, 0])


def test_nonfinite_global_score_raises():
    """Regression: a NaN/-inf global score used to silently keep the
    global model forever (every delta masked / NaN omegas). Broken server
    scoring is now an explicit error, not a frozen federation."""
    for bad in (float("nan"), float("-inf"), float("inf")):
        with pytest.raises(ValueError, match="global_score"):
            blendavg_weights([0.7, 0.9], global_score=bad)
    # candidate-side non-finite scores stay legal: they mask that
    # candidate only (a client that never finished reports -inf)
    w = blendavg_weights([float("nan"), 0.9], global_score=0.5)
    assert w[0] == 0.0 and w[1] == 1.0


def test_blendavg_weights_staleness_damping():
    """Async Eq. 9-10: staleness damps, renormalizes, and never resurrects
    a non-improver."""
    w = blendavg_weights([0.9, 0.9, 0.1], 0.5, staleness=[0, 8, 0],
                         staleness_exp=0.5)
    assert w[2] == 0.0  # still discarded
    np.testing.assert_allclose(w[1] / w[0], 3.0 ** -1, rtol=1e-12)
    np.testing.assert_allclose(w.sum(), 1.0)


# --------------------------------------------------------------- property --

@given(scores=st.lists(st.floats(-1, 1, allow_nan=False), min_size=1, max_size=16),
       gscore=st.floats(-1, 1, allow_nan=False))
@settings(max_examples=200, deadline=None)
def test_weights_properties(scores, gscore):
    w = blendavg_weights(scores, gscore)
    assert (w >= 0).all()
    # normalized iff any model improves
    if any(s > gscore for s in scores):
        np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-9)
        # discarding: w_i == 0 exactly for non-improving models
        for wi, si in zip(w, scores):
            assert (wi > 0) == (si > gscore)
        # order preservation: bigger delta -> bigger weight
        deltas = [s - gscore for s in scores]
        order = np.argsort(deltas)
        ws = w[order]
        assert (np.diff(ws) >= -1e-12).all()
    else:
        assert w.sum() == 0.0


@given(n=st.integers(1, 6), dim=st.integers(1, 32), seed=st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_blend_trees_is_convex_combination(n, dim, seed):
    """Blended leaf must stay inside the convex hull of candidate leaves."""
    rng = np.random.default_rng(seed)
    trees = [{"a": jnp.asarray(rng.normal(0, 1, dim).astype(np.float32))}
             for _ in range(n)]
    deltas = rng.random(n) + 1e-3
    omega = deltas / deltas.sum()
    out = np.asarray(blend_trees(trees, omega)["a"])
    stack = np.stack([np.asarray(t["a"]) for t in trees])
    assert (out <= stack.max(0) + 1e-5).all()
    assert (out >= stack.min(0) - 1e-5).all()


@given(seed=st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_blendavg_never_degrades_on_val(seed):
    """The defining invariant: post-aggregation val score >= global score
    when eval is exact (here: score = -||w - target||)."""
    rng = np.random.default_rng(seed)
    target = rng.normal(0, 1, 16).astype(np.float32)

    def ev(m):
        return -float(np.linalg.norm(np.asarray(m["w"]) - target))

    glob = {"w": jnp.asarray(rng.normal(0, 1, 16).astype(np.float32))}
    cands = [{"w": jnp.asarray(rng.normal(0, 1, 16).astype(np.float32))}
             for _ in range(4)]
    blended, info = blendavg(glob, cands, ev)
    # kept-global case trivially holds; blended case: convexity of the norm
    # guarantees the blend of improving models also improves
    assert ev(blended) >= ev(glob) - 1e-5

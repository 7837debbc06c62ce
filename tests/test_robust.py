"""Byzantine-robust aggregation (``repro.core.aggregate`` ROBUST family).

Pins the robust reducers against pure-numpy references, then their
statistical contracts as property tests (via ``hypothesis``):

- coordinate median / trimmed mean recover the honest mean within the
  honest spread whenever f < C/2 clients upload sign-flipped or
  100x-scaled updates — and median demonstrably BREAKS at f >= C/2 (the
  breakdown point is tight, not conservative);
- Krum's distance scores match the Blanchard et al. definition exactly,
  and multi-Krum keeps only honest candidates whenever f < (C-2)/2;
- the degenerate cases that make the defenses safe defaults: krum_mask
  at f = 0 is all-ones, median of identical candidates is that
  candidate, trimmed mean refuses n <= 2*trim.

Then the driver-level parity contract: with zero assumed attackers the
robust strategies ARE fedavg — krum bit-for-bit on the whole round
state, trimmed_mean bit-for-bit on the per-modality heads (its M head
documents uniform weighting instead of volume weighting) — and a robust
round keeps the stateless layout (no new state keys) and exactly one
compiled program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate
from repro.core.aggregate import StrategyConfig, make_strategy

from hypothesis import given, settings, strategies as st


# --------------------------------------------------------- numpy references --

def np_median(stack: np.ndarray) -> np.ndarray:
    return np.median(stack.astype(np.float32), axis=0)


def np_trimmed_mean(stack: np.ndarray, trim: int) -> np.ndarray:
    s = np.sort(stack.astype(np.float32), axis=0)
    return np.mean(s[trim:len(stack) - trim], axis=0)


def np_krum_scores(flat: np.ndarray, f: int) -> np.ndarray:
    """Blanchard et al. 2017: score(i) = sum of squared distances to
    candidate i's n - f - 2 nearest peers."""
    n = len(flat)
    d2 = np.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)
    np.fill_diagonal(d2, np.inf)
    k = max(n - f - 2, 1)
    return np.sort(d2, axis=1)[:, :k].sum(axis=1)


def _attacked_cohort(rng, n, f, dim, attack: str):
    """n candidates around a common honest mean; the last f are
    adversarial (sign-flipped or 100x-scaled). Returns (stack, honest)."""
    honest_mean = rng.normal(0, 1, dim).astype(np.float32)
    honest = honest_mean[None] + rng.normal(0, 0.1, (n, dim)).astype(np.float32)
    stack = honest.copy()
    bad = -honest[n - f:] if attack == "sign_flip" else 100.0 * honest[n - f:]
    stack[n - f:] = bad
    return stack, honest[: n - f]


# ------------------------------------------------- reducers vs references --

def test_median_tree_matches_numpy():
    rng = np.random.default_rng(0)
    tree = {"f": {"w": rng.normal(0, 1, (5, 3, 2)).astype(np.float32)},
            "g": {"b": rng.normal(0, 1, (5, 4)).astype(np.float32)}}
    out = aggregate.coordinate_median_tree(jax.tree.map(jnp.asarray, tree))
    for path in (("f", "w"), ("g", "b")):
        ref = np_median(tree[path[0]][path[1]])
        np.testing.assert_allclose(
            np.asarray(out[path[0]][path[1]]), ref, rtol=1e-6)


def test_trimmed_mean_tree_matches_numpy():
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (7, 4, 3)).astype(np.float32)
    out = aggregate.trimmed_mean_tree({"w": jnp.asarray(x)}, trim=2)
    np.testing.assert_allclose(np.asarray(out["w"]), np_trimmed_mean(x, 2),
                               rtol=1e-5)


def test_trimmed_mean_refuses_overtrim():
    x = {"w": jnp.ones((4, 2))}
    with pytest.raises(ValueError, match="2\\*trim"):
        aggregate.trimmed_mean_tree(x, trim=2)


def test_krum_scores_match_numpy_reference():
    rng = np.random.default_rng(2)
    tree = {"f": {"w": rng.normal(0, 1, (6, 3)).astype(np.float32)},
            "g": rng.normal(0, 1, (6, 2, 2)).astype(np.float32)}
    flat = np.concatenate([tree["f"]["w"].reshape(6, -1),
                           tree["g"].reshape(6, -1)], axis=1)
    for f in (0, 1):
        got = np.asarray(aggregate.krum_scores(
            jax.tree.map(jnp.asarray, tree), f))
        np.testing.assert_allclose(got, np_krum_scores(flat, f),
                                   rtol=1e-4, atol=1e-4)


def test_krum_mask_zero_f_is_identity():
    """f = 0 must short-circuit to all-ones without consulting scores —
    the bit-parity contract's foundation."""
    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.normal(0, 1, (4, 5)).astype(np.float32))}
    np.testing.assert_array_equal(np.asarray(aggregate.krum_mask(tree, 0)),
                                  np.ones(4, np.float32))


def test_median_of_identical_candidates_is_that_candidate():
    """All-honest degenerate case: when every client uploads the same
    model, the order statistic returns it exactly (= what fedavg would)."""
    row = np.random.default_rng(4).normal(0, 1, (3, 2)).astype(np.float32)
    stack = {"w": jnp.asarray(np.stack([row] * 5))}
    np.testing.assert_array_equal(
        np.asarray(aggregate.coordinate_median_tree(stack)["w"]), row)


# ---------------------------------------------------------- property tests --

@settings(max_examples=20, deadline=None)
@given(c=st.integers(5, 12), f_frac=st.floats(0.0, 0.99),
       attack=st.sampled_from(["sign_flip", "scale"]),
       seed=st.integers(0, 10_000))
def test_median_recovers_honest_mean_below_breakdown(c, f_frac, attack, seed):
    """f < C/2 arbitrary candidates cannot drag any coordinate of the
    median outside the honest envelope — so it stays within the honest
    spread of the honest mean."""
    f = int(f_frac * ((c - 1) // 2 + 1))  # 0 <= f <= floor((c-1)/2) < c/2
    stack, honest = _attacked_cohort(np.random.default_rng(seed), c, f, 6,
                                     attack)
    med = np.asarray(aggregate.coordinate_median_tree(
        {"w": jnp.asarray(stack)})["w"])
    assert np.all(med >= honest.min(axis=0) - 1e-6)
    assert np.all(med <= honest.max(axis=0) + 1e-6)
    tol = np.abs(honest - honest.mean(axis=0)).max() + 1e-6
    assert np.all(np.abs(med - honest.mean(axis=0)) <= tol)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(4, 12), seed=st.integers(0, 10_000))
def test_median_breakdown_point_is_tight(c, seed):
    """At f = ceil(C/2) colluding candidates the median IS corrupted —
    the f < C/2 guarantee is the breakdown point, not slack."""
    f = (c + 1) // 2
    rng = np.random.default_rng(seed)
    stack = rng.normal(0, 1, (c, 4)).astype(np.float32)
    honest_max = stack[: c - f].max()
    stack[c - f:] = 1e6
    med = np.asarray(aggregate.coordinate_median_tree(
        {"w": jnp.asarray(stack)})["w"])
    assert np.all(med > honest_max)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(5, 12), f_frac=st.floats(0.0, 0.99),
       attack=st.sampled_from(["sign_flip", "scale"]),
       seed=st.integers(0, 10_000))
def test_trimmed_mean_recovers_honest_mean(c, f_frac, attack, seed):
    """Trimming f per side with f malicious candidates leaves only
    honest values per coordinate, so the result lands in the honest
    envelope, within the honest spread of the honest mean."""
    f = int(f_frac * (((c - 1) // 2 - 1) + 1))  # n >= 2f + 1 and f < c/2
    stack, honest = _attacked_cohort(np.random.default_rng(seed), c, f, 6,
                                     attack)
    if f == 0:  # drivers route trim 0 to fedavg; reducer still defined
        return
    tm = np.asarray(aggregate.trimmed_mean_tree(
        {"w": jnp.asarray(stack)}, trim=f)["w"])
    assert np.all(tm >= honest.min(axis=0) - 1e-5)
    assert np.all(tm <= honest.max(axis=0) + 1e-5)
    tol = np.abs(honest - honest.mean(axis=0)).max() + 1e-5
    assert np.all(np.abs(tm - honest.mean(axis=0)) <= tol)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(5, 14), f_frac=st.floats(0.0, 0.99),
       seed=st.integers(0, 10_000))
def test_krum_excludes_outliers_below_breakdown(c, f_frac, seed):
    """f < (C-2)/2 far-away candidates always score worst: multi-Krum's
    n - f survivors are exactly the honest candidates, and the Krum
    pick (argmin score) is honest."""
    f_max = (c - 3) // 2  # largest f with f < (c-2)/2
    f = int(f_frac * (f_max + 1))
    if f == 0:
        return
    rng = np.random.default_rng(seed)
    stack, _ = _attacked_cohort(rng, c, 0, 6, "scale")
    # distinct large offsets: colluding-but-not-identical attackers
    stack[c - f:] += 50.0 * (1.0 + np.arange(f, dtype=np.float32))[:, None]
    tree = {"w": jnp.asarray(stack)}
    scores = np.asarray(aggregate.krum_scores(tree, f))
    assert int(np.argmin(scores)) < c - f
    mask = np.asarray(aggregate.krum_mask(tree, f))
    np.testing.assert_array_equal(mask[c - f:], np.zeros(f, np.float32))
    np.testing.assert_array_equal(mask[: c - f], np.ones(c - f, np.float32))


# ----------------------------------------------- config + driver contracts --

def test_robust_config_flags_and_validation():
    for name in aggregate.ROBUST:
        scfg = make_strategy(name, n_malicious=2)
        assert scfg.robust and not scfg.stateful and not scfg.client_active
        assert scfg.n_malicious == 2
    assert not make_strategy("fedavg").robust
    with pytest.raises(ValueError, match=">= 0"):
        StrategyConfig(name="krum", n_malicious=-1)


def test_sharded_spec_validates_robust_cohort_floor():
    from repro.core.federation_sharded import ShardedFedSpec

    kw = dict(n_clients=8, d_hidden=16, n_layers=1, seq_a=4, feat_a=3,
              seq_b=4, feat_b=3, out_dim=2, n_partial=8, n_frag=8,
              n_paired=8, n_val=16)
    with pytest.raises(ValueError, match="krum"):
        ShardedFedSpec(strategy="krum", n_malicious=1, n_sampled=3, **kw)
    with pytest.raises(ValueError, match="trimmed_mean"):
        ShardedFedSpec(strategy="trimmed_mean", n_malicious=2, n_sampled=4,
                       **kw)
    # at the floor both construct
    ShardedFedSpec(strategy="krum", n_malicious=1, n_sampled=4, **kw)
    ShardedFedSpec(strategy="trimmed_mean", n_malicious=2, n_sampled=5, **kw)


def _tiny_spec(**overrides):
    from repro.core.federation_sharded import ShardedFedSpec

    kw = dict(n_clients=4, d_hidden=16, n_layers=1, seq_a=4, feat_a=3,
              seq_b=4, feat_b=3, out_dim=2, n_partial=8, n_frag=8,
              n_paired=8, n_val=16)
    kw.update(overrides)
    return ShardedFedSpec(**kw)


def _tiny_batch(spec, rng):
    from repro.core.federation_sharded import batch_specs

    batch = {}
    for k, sd in batch_specs(spec).items():
        if k == "perm_b":
            batch[k] = jnp.asarray(rng.permutation(
                spec.n_clients * spec.n_frag).astype(np.int32))
        elif k.endswith("_y") or k.startswith("partial_y") or k == "val_y":
            batch[k] = jnp.asarray(
                (rng.random(sd.shape) < 0.3).astype(np.float32))
        elif k in ("partial_ma", "partial_mb", "paired_m", "frag_w"):
            # full rows everywhere: equal volumes, so fedavg's weights
            # normalize to exactly 1/K (the trimmed-parity premise)
            batch[k] = jnp.ones(sd.shape, jnp.float32)
        else:
            batch[k] = jnp.asarray(rng.normal(0, 1, sd.shape).astype(np.float32))
    return batch


def _run_rounds(spec, n=2):
    from repro.core.federation_sharded import (
        init_round_state, make_blendfl_round)

    rf = jax.jit(make_blendfl_round(spec))
    state = init_round_state(jax.random.PRNGKey(0), spec)
    for r in range(n):
        state, _ = rf(state, _tiny_batch(spec, np.random.default_rng(r)))
    return state, rf


def test_robust_rounds_are_stateless_single_program():
    """No new state keys (old checkpoints stay loadable) and one
    compiled program across rounds — robustness is static structure."""
    from repro.core.federation_sharded import init_round_state

    for name in aggregate.ROBUST:
        spec = _tiny_spec(strategy=name, n_malicious=1)
        assert "strat" not in init_round_state(jax.random.PRNGKey(0), spec)
        state, rf = _run_rounds(spec)
        assert "strat" not in state
        assert rf._cache_size() == 1


def test_krum_zero_malicious_is_fedavg_bitexact():
    """n_malicious = 0: the survivor mask is all-ones, so the entire
    round state (every head, both optimizers) matches fedavg bit-for-bit."""
    a, _ = _run_rounds(_tiny_spec(strategy="fedavg"))
    b, _ = _run_rounds(_tiny_spec(strategy="krum", n_malicious=0))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_trimmed_mean_zero_malicious_matches_fedavg_heads():
    """trim 0 delegates to fedavg with uniform weights; on this
    equal-volume cohort the per-modality heads of one round are
    bit-identical to fedavg. The M head documents uniform weighting over
    the K+1 candidates where fedavg volume-weights the server candidate,
    so it is excluded — and since the multimodal phase couples every
    head to g_M from round 2 on, the bit claim is a one-round claim."""
    a, _ = _run_rounds(_tiny_spec(strategy="fedavg"), n=1)
    b, _ = _run_rounds(_tiny_spec(strategy="trimmed_mean", n_malicious=0), n=1)
    for head in ("f_A", "f_B", "g_A", "g_B"):
        for x, y in zip(jax.tree.leaves(a["global_models"][head]),
                        jax.tree.leaves(b["global_models"][head])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_robust_round_survives_attacked_uplink():
    """End-to-end sanity: with spec.attacks on and one sign-flipping
    candidate in the coef vector, a robust round still produces finite
    global models, and the honest-coef round differs from the attacked
    one (the hook is live, not a no-op)."""
    spec = _tiny_spec(strategy="median", n_sampled=4, attacks=True)
    from repro.core.federation_sharded import (
        init_round_state, make_blendfl_round)

    rf = jax.jit(make_blendfl_round(spec))
    batch = _tiny_batch(spec, np.random.default_rng(0))
    batch["sampled"] = jnp.arange(4, dtype=jnp.int32)
    state = init_round_state(jax.random.PRNGKey(0), spec)
    honest = dict(batch, attack_coef=jnp.ones(4, jnp.float32))
    flipped = dict(batch,
                   attack_coef=jnp.asarray([-1.0, 1.0, 1.0, 1.0], jnp.float32))
    sa, _ = rf(state, honest)
    sb, _ = rf(state, flipped)
    assert rf._cache_size() == 1  # the coef is data, not structure
    leaves_a = jax.tree.leaves(sa["global_models"])
    leaves_b = jax.tree.leaves(sb["global_models"])
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves_b)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(leaves_a, leaves_b))

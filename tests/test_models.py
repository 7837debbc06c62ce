"""Model-layer unit tests: attention paths, RoPE, MoE, recurrent cells."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    causal_mask,
    chunked_gqa_sdpa,
    gqa_sdpa,
)
from repro.models.recurrent import (
    gated_linear_scan,
    gated_linear_scan_ref,
    gated_linear_step,
    slstm_init,
    slstm_scan,
    slstm_step,
)
from repro.models.rope import apply_rope, mrope_positions, rope_angles, text_positions


# ---------------------------------------------------------------- attention --

@pytest.mark.slow
@given(sq=st.integers(8, 80), skx=st.integers(0, 40), hkv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 3]), window=st.sampled_from([0, 7, 16]),
       seed=st.integers(0, 100))
@settings(max_examples=40, deadline=None)
def test_chunked_attention_equals_einsum(sq, skx, hkv, g, window, seed):
    sk = sq + skx
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, sq, hkv * g, 16))
    k = jax.random.normal(ks[1], (1, sk, hkv, 16))
    v = jax.random.normal(ks[2], (1, sk, hkv, 16))
    mask = causal_mask(sq, sk, window, q_offset=sk - sq)
    ref = gqa_sdpa(q, k, v, mask)
    out = chunked_gqa_sdpa(q, k, v, causal=True, window=window, q_offset=sk - sq,
                           block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-4)


@pytest.mark.slow
def test_chunked_attention_gradients_match():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 6, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))

    def f_chunk(q, k, v):
        return jnp.sum(chunked_gqa_sdpa(q, k, v, causal=True, block_q=16,
                                        block_k=16) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(gqa_sdpa(q, k, v, causal_mask(64, 64)) ** 2)

    g1 = jax.grad(f_chunk, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


@pytest.mark.slow
def test_gqa_grouping_matches_repeated_heads():
    """GQA-grouped einsum == materializing repeated KV heads."""
    from repro.models.attention import _repeat_kv

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 32, 8, 16))
    k = jax.random.normal(ks[1], (2, 32, 2, 16))
    v = jax.random.normal(ks[2], (2, 32, 2, 16))
    mask = causal_mask(32, 32)
    out = gqa_sdpa(q, k, v, mask)
    ref = gqa_sdpa(q, _repeat_kv(k, 4), _repeat_kv(v, 4), mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------- rope --

def test_rope_preserves_norm_and_relativity():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 32))
    pos = text_positions(1, 8)
    ang = rope_angles(pos, 32, 10000.0)
    y = apply_rope(x, ang)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # relativity: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 32))
    dots = []
    for p in (0, 5, 11):
        aq = rope_angles(jnp.array([[p]]), 32, 10000.0)
        ak = rope_angles(jnp.array([[p + 3]]), 32, 10000.0)
        dots.append(float(jnp.sum(apply_rope(q, aq) * apply_rope(k, ak))))
    np.testing.assert_allclose(dots, dots[0], rtol=1e-4)


def test_mrope_text_rows_reduce_to_1d_rope():
    """Text tokens use t=h=w so M-RoPE must equal standard RoPE."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 2, 32))
    pos1d = text_positions(1, 6, offset=4)
    pos3d = jnp.stack([pos1d, pos1d, pos1d], axis=-1)
    a1 = rope_angles(pos1d, 32, 1e4)
    a3 = rope_angles(pos3d, 32, 1e4, sections=(6, 5, 5))
    np.testing.assert_allclose(np.asarray(apply_rope(x, a1)),
                               np.asarray(apply_rope(x, a3)), rtol=1e-5, atol=1e-6)


def test_mrope_positions_layout():
    pos = mrope_positions(2, 9, 4)
    assert pos.shape == (2, 13, 3)
    assert (np.asarray(pos[0, :9, 0]) == 0).all()  # vision t=0
    txt = np.asarray(pos[0, 9:])
    assert (txt[:, 0] == txt[:, 1]).all() and (txt[:, 1] == txt[:, 2]).all()


# ---------------------------------------------------------------- recurrent --

@pytest.mark.slow
@given(s=st.integers(4, 96), chunk=st.sampled_from([4, 16, 64]),
       normalize=st.booleans(), seed=st.integers(0, 50))
@settings(max_examples=30, deadline=None)
def test_gated_linear_scan_chunkwise_equals_sequential(s, chunk, normalize, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, 2, s, 8))
    k = jax.random.normal(ks[1], (1, 2, s, 8)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, s, 8))
    lf = -jnp.abs(jax.random.normal(ks[3], (1, 2, s))) * 0.3
    out = gated_linear_scan(q, k, v, lf, chunk=chunk, normalize=normalize)
    ref = gated_linear_scan_ref(q, k, v, lf, normalize=normalize)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-3)


def test_gated_linear_state_handoff():
    """scan(return_state) + step must continue the sequence exactly."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    s = 33
    q = jax.random.normal(ks[0], (1, 2, s, 8))
    k = jax.random.normal(ks[1], (1, 2, s, 8)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, s, 8))
    lf = -jnp.abs(jax.random.normal(ks[3], (1, 2, s))) * 0.2
    full = gated_linear_scan_ref(q, k, v, lf)
    _, state = gated_linear_scan(q[:, :, :-1], k[:, :, :-1], v[:, :, :-1],
                                 lf[:, :, :-1], chunk=8, return_state=True)
    h_last, _ = gated_linear_step(q[:, :, -1], k[:, :, -1], v[:, :, -1],
                                  lf[:, :, -1], state)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(full[:, :, -1]),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.slow
def test_slstm_step_equals_scan():
    p = slstm_init(jax.random.PRNGKey(0), 32, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
    full, _ = slstm_scan(p, x, 4)
    zero = jnp.zeros((2, 4, 8))
    state = (zero, zero, zero - 1e30, zero)  # c, n, m, h_prev
    outs = []
    for t in range(10):
        h, state = slstm_step(p, x[:, t], 4, state)
        outs.append(h)
    step_out = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step_out), np.asarray(full),
                               atol=1e-5, rtol=1e-4)


def test_moe_all_tokens_routed_with_ample_capacity():
    """With capacity >= T*k/E tokens nothing is dropped: MoE output must
    equal the dense mixture-of-selected-experts reference."""
    from repro.models.config import ArchConfig
    from repro.models.moe import moe_apply, moe_init
    from repro.models.mlp import mlp

    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                     n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=4, top_k=2,
                     capacity_factor=8.0, act="swiglu")
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
    out, aux = moe_apply(p, cfg, x)

    # dense reference: route every token through its top-k experts
    xf = np.asarray(x.reshape(12, 16))
    logits = xf @ np.asarray(p["router"]["w"])
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    gate, idx = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    ref = np.zeros((12, 16), np.float32)
    for t in range(12):
        for j in range(2):
            e = int(idx[t, j])
            ep = jax.tree.map(lambda w, e=e: w[e], p["experts"])
            ref[t] += float(gate[t, j]) * np.asarray(
                mlp(ep, jnp.asarray(xf[t:t+1]), "swiglu"))[0]
    np.testing.assert_allclose(np.asarray(out).reshape(12, 16), ref,
                               atol=1e-4, rtol=1e-3)
    assert float(aux) > 0


@pytest.mark.slow
def test_moe_grouped_equals_flat():
    """GShard-style grouped dispatch (§Perf B.2) must match the flat path
    when capacity is ample (per-group capacity changes drop behavior only
    under overflow)."""
    from repro.models.config import ArchConfig
    from repro.models.moe import _moe_flat, _moe_grouped, moe_init

    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                     n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=4, top_k=2,
                     capacity_factor=8.0, act="swiglu", moe_groups=4)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
    o1, a1 = _moe_flat(p, cfg, x)
    o2, a2 = _moe_grouped(p, cfg, x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-6)

"""The MLP encoder f_m: x (B, S, F) -> tanh(x W_in + b) averaged over S,
then ``n_layers`` residual blocks h + gelu(h W + b), then RMS norm (eps
1e-5) with a learned gain.

Weights: the encoder's key splits into n_layers + 2 (input layer, hidden
layers, one unused); each weight is N(0, 1/d_in), each bias 0 and the
norm's gain 1.
"""
import jax
import jax.numpy as jnp

from bench.reference import dense_init, gelu, linear


def init(key, feat: int, cfg: dict) -> dict:
    d, n = cfg["d_hidden"], cfg["n_layers"]
    ks = jax.random.split(key, n + 2)
    return {"in": dense_init(ks[0], feat, d),
            "hidden": [dense_init(ks[i + 1], d, d) for i in range(n)],
            "norm": {"g": jnp.ones((d,), jnp.float32)}}


def apply(p, x, cfg: dict, precision: str):
    """(B, S, F) -> (B, d), every product in ``precision``."""
    h = jnp.mean(jnp.tanh(linear(p["in"], x, precision)), axis=-2)
    for layer in p["hidden"]:
        h = h + gelu(linear(layer, h, precision))
    return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + 1e-5) \
        * p["norm"]["g"]


def _products(cfg: dict, side: str) -> tuple:
    """(input-layer, hidden-layers) forward FLOPs of one row."""
    d = cfg["d_hidden"]
    seq, feat = cfg[f"seq_{side}"], cfg[f"feat_{side}"]
    return 2 * seq * feat * d, cfg["n_layers"] * 2 * d * d


def forward_flops(cfg: dict, side: str) -> int:
    return sum(_products(cfg, side))


def train_flops(cfg: dict, side: str) -> int:
    first, hidden = _products(cfg, side)
    return 2 * first + 3 * hidden  # fwd + weight grad; hidden adds input grad


def n_params(cfg: dict, feat: int) -> int:
    d, n = cfg["d_hidden"], cfg["n_layers"]
    return feat * d + d + n * (d * d + d) + d

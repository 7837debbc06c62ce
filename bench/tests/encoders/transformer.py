"""Test-only encoder module: the program's toy attention block
(``enc_type="transformer"`` in ``repro.core.encoders``), rewritten in plain
``jax.numpy`` from its equations.

f_m: x (B, S, F) -> h = tanh(x W_in + b); one pre-norm attention block
over the S positions with ``N_HEADS`` heads, h + softmax(q k^T / sqrt(d_h))
v with q, k, v = RMS(h) W_q, W_k, W_v (no biases); then h + gelu(h W_ff +
b); the mean over S; RMS norm (eps 1e-5) with a learned gain.

Weights: the encoder's key splits into n_layers + 2; keys 0-4 draw W_in,
W_q, W_k, W_v and W_ff in that order (n_layers must be 3 or more); each
weight is N(0, 1/d_in), each bias 0, each norm gain 1.
"""
import math

import jax
import jax.numpy as jnp

from bench.reference import dense_init, gelu, linear, mm

N_HEADS = 4  # repro.core.encoders.EncoderConfig's default


def init(key, feat: int, cfg: dict) -> dict:
    d = cfg["d_hidden"]
    ks = jax.random.split(key, cfg["n_layers"] + 2)
    ones = lambda: {"g": jnp.ones((d,), jnp.float32)}  # noqa: E731
    square = lambda k: {"w": dense_init(k, d, d)["w"]}  # noqa: E731
    return {"in": dense_init(ks[0], feat, d), "ln": ones(),
            "wq": square(ks[1]), "wk": square(ks[2]), "wv": square(ks[3]),
            "ff": dense_init(ks[4], d, d), "norm": ones()}


def _rms(g, x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-5) * g


def apply(p, x, cfg: dict, precision: str):
    """(B, S, F) -> (B, d), every product in ``precision``."""
    h = jnp.tanh(linear(p["in"], x, precision))
    hn = _rms(p["ln"]["g"], h)
    b, s, d = hn.shape
    hd = d // N_HEADS

    def heads(w):  # (B, S, d) -> (B, heads, S, d_h)
        return mm(hn, w, precision).reshape(b, s, N_HEADS, hd) \
            .transpose(0, 2, 1, 3)
    q, k, v = heads(p["wq"]["w"]), heads(p["wk"]["w"]), heads(p["wv"]["w"])
    att = jax.nn.softmax(mm(q, k.transpose(0, 1, 3, 2), precision)
                         / math.sqrt(hd), axis=-1)
    h = h + mm(att, v, precision).transpose(0, 2, 1, 3).reshape(b, s, d)
    h = h + gelu(linear(p["ff"], h, precision))
    return _rms(p["norm"]["g"], jnp.mean(h, axis=-2))


def _products(cfg: dict, side: str) -> tuple:
    """(input-projection, other) forward FLOPs of one row."""
    d, s = cfg["d_hidden"], cfg[f"seq_{side}"]
    return (2 * s * cfg[f"feat_{side}"] * d,
            4 * 2 * s * d * d + 2 * 2 * s * s * d)  # q, k, v, ff; qk^T, att v


def forward_flops(cfg: dict, side: str) -> int:
    return sum(_products(cfg, side))


def train_flops(cfg: dict, side: str) -> int:
    first, rest = _products(cfg, side)
    return 2 * first + 3 * rest


def n_params(cfg: dict, feat: int) -> int:
    d = cfg["d_hidden"]
    return feat * d + d + d + 3 * d * d + d * d + d + d

"""Operations and bytes the algorithm needs, counted from shapes.

Model FLOPs count the matrix products only (2 per multiply-add); the
element-wise work (tanh, gelu, norms, losses, optimizer) is left out.
A training row pays its forward products and, in the backward pass, the
weight gradient of every product plus the input gradient of every product
whose input is not data: the input layer of an encoder reads the raw
features, so no gradient flows into them. The encoder's terms come from
its module (``bench/encoders/<enc_type>.py``), counted by the same rule.
"""
from __future__ import annotations

from bench.common import encoder


def _head(cfg: dict) -> int:
    return 2 * cfg["d_hidden"] * cfg["n_labels"]


def _fusion(cfg: dict) -> int:
    d = cfg["d_hidden"]
    return 2 * (2 * d) * d + 2 * d * cfg["n_labels"]


def forward_row(cfg: dict, route: str) -> int:
    """Forward FLOPs of one row on a serving route."""
    enc = encoder(cfg)
    enc_a, enc_b = enc.forward_flops(cfg, "a"), enc.forward_flops(cfg, "b")
    if route == "unimodal_A":
        return enc_a + _head(cfg)
    if route == "unimodal_B":
        return enc_b + _head(cfg)
    if route in ("multimodal", "vfl_fallback"):
        return enc_a + enc_b + _fusion(cfg)
    raise ValueError(f"unknown route {route!r}")


def n_params(cfg: dict) -> int:
    """Parameters of one client's model set {f_A, f_B, g_A, g_B, g_M}."""
    d, out = cfg["d_hidden"], cfg["n_labels"]
    enc = encoder(cfg)
    return (enc.n_params(cfg, cfg["feat_a"]) + enc.n_params(cfg, cfg["feat_b"])
            + 2 * (d * out + out)
            + (2 * d * d + d) + (d * out + out))


def train_round(cfg: dict, live: dict, n_val: int) -> int:
    """Model FLOPs of one full-participation BlendAvg round.

    ``live``: per-client live rows of each slab (partial_a, partial_b,
    paired) and the number of aligned fragmented pairs. Scoring evaluates
    C candidates and the global model per modality on ``n_val`` rows, and
    C + 2 fusion heads (C clients, the server head, the global) over one
    pass of the blended encoders; the blend is one multiply-add per
    candidate and parameter."""
    C = len(live["paired"])
    enc = encoder(cfg)
    tr_a, tr_b = enc.train_flops(cfg, "a"), enc.train_flops(cfg, "b")
    uni_a = tr_a + 3 * _head(cfg)
    uni_b = tr_b + 3 * _head(cfg)
    both = tr_a + tr_b + 3 * _fusion(cfg)
    train = (sum(live["partial_a"]) * uni_a + sum(live["partial_b"]) * uni_b
             + (sum(live["paired"]) + live["aligned"]) * both)
    enc_a, enc_b = enc.forward_flops(cfg, "a"), enc.forward_flops(cfg, "b")
    score = n_val * ((C + 1) * (enc_a + _head(cfg) + enc_b + _head(cfg))
                     + enc_a + enc_b + (C + 2) * _fusion(cfg))
    blend = 2 * (C + 1) * n_params(cfg)
    return int(train + score + blend)


def wire_codec_bytes(rows: int, n: int) -> int:
    """HBM bytes of one wire-codec kernel call over (rows, n) float32
    messages: read the rows and their [scale, threshold] pairs, write the
    decoded rows."""
    return 4 * (2 * rows * n + 2 * rows)

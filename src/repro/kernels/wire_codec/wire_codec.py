"""Fused quantize + sparsify + dequantize wire-codec pass as a Pallas kernel.

Simulated lossy wire round-trip for one batch of flattened messages
(rows = client candidates on the uplink, a single row on the downlink).
Given per-row symmetric int8 scales and top-k magnitude thresholds
(computed outside by one batched ``lax.top_k`` over |x| — a data-
dependent exact top-k scatter is not expressible as a single streaming
pass, but threshold-select is), the kernel applies the whole
encode->decode pipeline in ONE pass over each element:

    keep = |x| >= thresh            # magnitude top-k sparsification
    q    = clip(round(x * 127/s))   # symmetric int8 quantization
    out  = where(keep, q * s/127, 0)

so the round is memory-bound at exactly one read + one write per
parameter, instead of the three materialized passes (scale, quantize,
mask) a naive composition of the codecs would issue.

Grid: (row tiles, column tiles). A row tile is all L rows when L <= 8
(a block dimension equal to the array's) and 8 rows otherwise, with L
padded up to a multiple of 8; a column tile is ``block_n`` lanes, a
multiple of 128, or the whole row when it is shorter. Per program, VMEM
holds one (rows, block_n) tile plus those rows' (rows, 2) [scale,
thresh] pairs, so its footprint does not grow with L. ``quantize`` is a
static flag: the pure top-k codec skips the rounding so that frac=1.0 is
bit-exact identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_ROWS = 8  # sublanes of a float32 vreg: Mosaic's row-tile unit
_LANES = 128


def _kernel(x_ref, st_ref, o_ref, *, quantize):
    x = x_ref[...].astype(jnp.float32)  # (rows, block_n)
    scale = st_ref[:, 0:1]  # (rows, 1)
    thresh = st_ref[:, 1:2]
    keep = jnp.abs(x) >= thresh
    if quantize:
        q = jnp.clip(jnp.round(x * (127.0 / scale)), -127.0, 127.0)
        x = q * (scale * (1.0 / 127.0))
    o_ref[...] = jnp.where(keep, x, 0.0).astype(o_ref.dtype)


def wire_codec_pallas(x, scale_thresh, *, quantize: bool,
                      block_n: int = 2048, interpret: bool = False):
    """x (L, N) rows; scale_thresh (L, 2) per-row [scale, thresh].

    Returns the (L, N) decoded reconstruction (same dtype as x).
    """
    l, n = x.shape
    rows = l if l <= _ROWS else _ROWS
    pad_l = (-l) % rows
    if n <= block_n:
        block_n = n
    else:
        block_n = pl.cdiv(block_n, _LANES) * _LANES
    pad_n = (-n) % block_n
    # zero pad: padded lanes decode to 0 and padded rows (scale 1) to 0;
    # both are sliced off
    if pad_l or pad_n:
        x = jnp.pad(x, ((0, pad_l), (0, pad_n)))
    st = scale_thresh.astype(jnp.float32)
    if pad_l:
        st = jnp.pad(st, ((0, pad_l), (0, 0)), constant_values=1.0)
    grid = ((l + pad_l) // rows, (n + pad_n) // block_n)
    out = pl.pallas_call(
        functools.partial(_kernel, quantize=quantize),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((rows, 2), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, st)
    return out[:l, :n]

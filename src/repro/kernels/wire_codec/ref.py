"""Pure-jnp oracle for the fused wire-codec round-trip kernel."""
from __future__ import annotations

import jax.numpy as jnp


def wire_codec_ref(x, scale_thresh, *, quantize: bool):
    """x (L, N); scale_thresh (L, 2) per-row [int8 scale, top-k |x|
    threshold]. Returns the decoded (L, N) reconstruction: entries with
    |x| < thresh are dropped (sent as implicit zeros); kept entries are
    optionally round-tripped through symmetric int8 at q = round(x *
    127/scale), dequantized as q * (scale * (1/127)). The step is spelled
    as a multiply by the constant 1/127, as XLA rewrites a divide by a
    constant inside jit, so eager and jitted calls agree bit for bit."""
    xf = x.astype(jnp.float32)
    scale = scale_thresh[:, 0:1].astype(jnp.float32)
    thresh = scale_thresh[:, 1:2].astype(jnp.float32)
    keep = jnp.abs(xf) >= thresh
    if quantize:
        q = jnp.clip(jnp.round(xf * (127.0 / scale)), -127.0, 127.0)
        xf = q * (scale * (1.0 / 127.0))
    return jnp.where(keep, xf, 0.0).astype(x.dtype)

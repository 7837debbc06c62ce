"""Public wrapper: blend a pytree (or flat array) of stacked client params."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.blendavg.blendavg import blend_params_pallas


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _blend(stacked, omega, block_n, interpret):
    if isinstance(stacked, jnp.ndarray) or hasattr(stacked, "shape"):
        return blend_params_pallas(stacked, omega, block_n=block_n,
                                   interpret=interpret)

    def blend_leaf(leaf):
        l = leaf.shape[0]
        flat = leaf.reshape(l, -1)
        out = blend_params_pallas(flat, omega, block_n=block_n, interpret=interpret)
        return out.reshape(leaf.shape[1:])

    return jax.tree.map(blend_leaf, stacked)


def blend_params(stacked, omega, *, block_n: int = 2048):
    """stacked: (L, N) array OR pytree whose leaves have leading dim L.
    omega (L,) masked blend weights. Returns blended array / pytree."""
    return _blend(stacked, omega, block_n, not on_tpu())

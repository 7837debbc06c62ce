"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling
rules or its VMEM limit; compiling for a described, unattached v5e
chip can. Shapes are those of the widest configuration the SPMD round
runs (MLP encoders at d_hidden 256, 16 clients):

- the wire codec at the uplink (16 candidate rows) and the downlink
  (1 row) of the largest model leaf, and at serving capacities 2 and 4
  over one d_hidden-wide feature row;
- the blendavg kernel over 16 stacked copies of that leaf.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a pytest-xdist worker that did so
at collection would leave the others with different test lists.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codec import topk_k
from repro.kernels.blendavg import ops as blend_ops
from repro.kernels.wire_codec import ops as codec_ops

D_HIDDEN = 256
LEAF = 2 * D_HIDDEN * D_HIDDEN  # the fusion head's "mix" weight
CLIENTS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,n", [
    (CLIENTS, LEAF),  # uplink: one candidate row per client
    (1, LEAF),  # downlink: the broadcast global
    (2, D_HIDDEN),  # VFL serving, capacity 2
    (4, D_HIDDEN),  # VFL serving, capacity 4
])
def test_wire_codec_compiles_for_v5e(one_chip, monkeypatch, rows, n):
    monkeypatch.setattr(codec_ops, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda x: codec_ops.wire_codec_roundtrip(
        x, k=topk_k(n, 0.25), quantize=True))
    compiled = fn.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_blendavg_compiles_for_v5e(one_chip, monkeypatch):
    monkeypatch.setattr(blend_ops, "on_tpu", lambda: True)
    stacked = jax.ShapeDtypeStruct((CLIENTS, LEAF), jnp.float32,
                                   sharding=one_chip)
    omega = jax.ShapeDtypeStruct((CLIENTS,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(blend_ops.blend_params).lower(stacked, omega).compile()
    assert "tpu_custom_call" in compiled.as_text()

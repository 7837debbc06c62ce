"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships as a subpackage:  <name>/<name>.py (pl.pallas_call +
BlockSpec VMEM tiling), <name>/ops.py (jit'd public wrapper), and
<name>/ref.py (pure-jnp oracle used by the sweep tests).

On the CPU backend kernels execute with interpret=True (the kernel body
runs in Python), which is how the tests check them; on TPU the same
pallas_call lowers through Mosaic. Any other backend is an error: a
kernel silently interpreted on an accelerator would hide that the run
never used the chip.
"""
import functools

import jax


@functools.cache
def on_tpu() -> bool:
    """Shared backend probe for the jit'd kernel wrappers: True on TPU
    (compile through Mosaic), False on CPU (interpret), and a
    RuntimeError on any other backend.

    The backend cannot change within a process, so the probe is cached:
    wrappers decide ``interpret=not on_tpu()`` once instead of calling
    ``jax.default_backend()`` (which walks the backend registry) on
    every trace. Defined above the subpackage imports so that ops
    modules can ``from repro.kernels import on_tpu`` without a cycle.
    """
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels here compile for TPU or run interpreted on "
            f"CPU; the {backend!r} backend is neither")
    return backend == "tpu"


from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.blendavg.ops import blend_params  # noqa: E402
from repro.kernels.mlstm_scan.ops import mlstm_scan  # noqa: E402
from repro.kernels.wire_codec.ops import wire_codec_roundtrip  # noqa: E402

__all__ = ["on_tpu", "flash_attention", "blend_params", "mlstm_scan",
           "wire_codec_roundtrip"]

"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's phases are called directly with small ``Sizes``; its
device phase, which demands a TPU, is checked to refuse the CPU. On the
CPU the Pallas kernels run interpreted, so phase (c) expects no
``tpu_custom_call`` here (and requires one on the chip). The four-chip
phase runs in a child process that sees four virtual CPU devices.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parents[1]
_PATH = _ROOT / "chip_smoke.py"

_FOUR_DEVICES = """
import dataclasses, jax, pytest
import chip_smoke as cs
sz = cs.Sizes(clients=8, rows_cap=8, n_train=512, n_val=64, d_hidden=16,
              n_layers=1)
out = cs.phase_four_chips(sz, jax.devices(), log=lambda _: None)
assert out["worst_rel"] <= cs.MESH_LOSS_RTOL, out
odd = cs.train_args(dataclasses.replace(sz, clients=6), "--rounds", "1")
with pytest.raises(ValueError, match="does not divide"):
    cs.tf.build_federation(odd)
print("four-device phase ok")
"""


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sizes(cs):
    return cs.Sizes(clients=4, rows_cap=8, n_train=512, n_val=64, d_hidden=16,
                    n_layers=1, n_sampled=2, requests=6, rows=3)


@pytest.fixture(scope="module")
def trained(cs, sizes, tmp_path_factory):
    ckpt = str(tmp_path_factory.mktemp("ckpt"))
    return ckpt, cs.phase_train(sizes, ckpt, log=lambda _: None)


def test_device_phase_refuses_cpu(cs):
    with pytest.raises(AssertionError, match="no TPU"):
        cs.phase_device(1)


def test_train_phase(trained):
    _, history = trained
    assert [h["round"] for h in history] == [0, 1, 2]


def test_codec_phase(cs, sizes):
    assert len(cs.phase_codec(sizes, log=lambda _: None)) == 2


def test_serve_phase(cs, sizes, trained):
    worst = cs.phase_serve(sizes, trained[0], log=lambda _: None)
    assert set(worst) == {"none", "int8_topk"}


def test_inhost_phase(cs, sizes):
    assert len(cs.phase_inhost(sizes, log=lambda _: None)) == 2


def test_collectives_counts_hlo_ops(cs):
    hlo = ("%a = f32[4] all-reduce(f32[4] %x)\n"
           "%b = f32[8] all-gather-start(f32[2] %y)\n"
           "%c = f32[8] all-gather(f32[2] %z)\n")
    assert cs.collectives(hlo) == {"all-reduce": 1, "all-gather": 2}


def test_four_chip_phase_on_virtual_devices():
    """The (4, 1) mesh matches one device, compiles its round once, and
    a K the data axis does not divide is refused."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(_ROOT), str(_ROOT / "src")])}
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                       cwd=_ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "four-device phase ok" in r.stdout

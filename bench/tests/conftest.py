"""The benchmark's CPU tests: tiny sizes, no TPU."""
import json
import sys
from pathlib import Path

import jax
import pytest

_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, run  # noqa: E402

SEED = 2**31 + 3
V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def with_pending() -> dict:
    """BENCHMARK.json with the entries of the cells kept in
    ``bench/pending`` (cells whose harness is tested but which the
    benchmark does not run yet)."""
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    for path in sorted((common.BENCH / "pending").glob("*.json")):
        extra = common.load_json(path)
        for key in ("workloads", "end_to_end", "per_layer"):
            spec[key] = spec[key] + extra[key]
    return spec


_resolve = common.resolve  # the harness fixture patches common.resolve


def RESOLVE(cell: str) -> dict:
    return _resolve(cell, spec=with_pending())


def tiny(cell: str, **config) -> dict:
    """The cell at a tiny size, with ``config`` over its configuration."""
    info = RESOLVE(cell)
    info["config"] = dict(info["config"], seq_a=6, feat_a=5, seq_b=4, feat_b=8,
                          d_hidden=16, n_clients=4, rows_cap=8, n_train=256,
                          n_val=128, **config)
    if info["traffic"]["kind"] == "serve":
        info["traffic"] = dict(info["traffic"], capacities=[2, 4, 16],
                               rate_per_s=150.0,
                               check_requests=40)
    return info


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench-cache")


@pytest.fixture
def harness(monkeypatch, cache, capsys):
    """run.main(argv) with tiny files and CPU devices -> (rc, result);
    ``config`` goes over the tiny configuration."""
    prec = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(common, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(common, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(common, "use_compile_cache", lambda: None)
    monkeypatch.setattr(common, "CACHE", cache)

    def go(cell, trace=0, **config):
        monkeypatch.setattr(common, "resolve", lambda w: tiny(cell, **config))
        capsys.readouterr()
        rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1", "--trace", str(trace)])
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(lines[-1])
    yield go
    jax.config.update("jax_default_matmul_precision", prec)



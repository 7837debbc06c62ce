"""What every cell shares: the benchmark file, the cell's configuration and
traffic files, the peaks table, the compile cache, and the result line."""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
ENCODERS = BENCH / "encoders"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, bench_file: Path = ROOT / "BENCHMARK.json",
            spec: dict | None = None) -> dict:
    """The cell named ``workload`` with its configuration, traffic, limits
    and metric entries, each read from its own file. ``spec`` stands in
    for the benchmark file's contents."""
    spec = load_json(bench_file) if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")

    def here(m):
        return workload in m.get("workloads", [workload])
    e2e = [m for m in spec["end_to_end"] if here(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if here(m) and m["moves"] in moved]
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer}


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def encoder(cfg: dict):
    """The module ``bench/encoders/<enc_type>.py`` of the configuration's
    encoder f_m: its ``init``, ``apply``, ``forward_flops``,
    ``train_flops`` and ``n_params``. A configuration that names no encoder,
    as the hand-counted ones of ``test_bench_flops`` do, has the MLP. One
    whose encoder has no module fails here, naming the missing file."""
    name = cfg.get("enc_type", "mlp")
    path = ENCODERS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"encoder {name!r} has no module: {path} is missing")
    return _load(str(path))


def encoder_args(cfg: dict) -> dict:
    """Every setting of the program's ``EncoderConfig`` that the
    configuration sets, under the same key: an encoder's hyperparameters
    reach the program as data, with no edit here for a new one."""
    import dataclasses

    from repro.core.encoders import EncoderConfig

    return {f.name: cfg[f.name] for f in dataclasses.fields(EncoderConfig)
            if f.name in cfg}


@functools.lru_cache(maxsize=None)
def _load(path: str):
    name = "bench_encoder_" + Path(path).stem
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def use_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` points), every program kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_info(devs: list) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def annotate(obj, name: str, label: str) -> None:
    """Wrap ``obj.name`` in a host span called ``label``, on this instance
    only: the program itself is not changed."""
    import jax

    fn = getattr(obj, name)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **k)
    setattr(obj, name, wrapped)


class Clock:
    """Host clock read from process start."""

    def __init__(self, t0: float | None = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def since(self) -> float:
        return time.perf_counter() - self.t0


def compare(readings: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): each number at or under its limit.
    A number that is missing or not finite fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and v == v and abs(v) != float("inf") and v <= limit
        ok = ok and good
        out[name] = {"value": v, "limit": limit}
    return ok, out


def emit(result: dict) -> None:
    """Numbers compared as the last lines of stderr, then the result as the
    last line of stdout (the ``compared`` key last)."""
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    compared = result.pop("compared")
    result["compared"] = compared
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

"""Smoke run of the federated round and the serving engine on the chip.

Drives the system's main paths once, through the entry points a user
calls, at the widest MLP encoder the SPMD round runs (d_hidden 256, the
paper backbone's d_model) on the 25-label MIMIC-conditions stand-in:

    (a) the device: JAX must report a TPU;
    (b) ``train_federated``: 16 clients x 256 rows, 3 rounds with a
        checkpoint — finite losses, ``loss_uni`` falls, one compiled round;
    (c) the same federation under ``--codec int8_topk --n-sampled 8`` —
        the compiled round holds the wire-codec kernel (``tpu_custom_call``);
    (d) ``serve_federated``: the checkpoint of (b) behind the serving
        engine, every request mix plus ``vfl_heavy`` under ``int8_topk`` —
        one program per (route, capacity), measured wire bytes equal the
        analytic count, scores agree with eager ``predict``;
    (e) the in-host ``Federation`` (the blendavg kernel's path), 2 rounds.

    python chip_smoke.py             # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4   # only: the (b) federation on a (4, 1)
                                     # mesh against a one-device mesh

Weights are random from fixed seeds and the data is synthetic, made from
seeds. Every check raises; the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import kernels  # noqa: E402
from repro.core.encoders import EncoderConfig  # noqa: E402
from repro.core.inference import (communication_cost, predict,  # noqa: E402
                                  request_rows)
from repro.data.synthetic import make_task  # noqa: E402
from repro.launch import serve_federated as sf  # noqa: E402
from repro.launch import train_federated as tf  # noqa: E402
from repro.launch.runtime import device_info, use_compile_cache  # noqa: E402

TASK = "conditions"

# Largest |engine score - eager predict score| admitted per VFL codec.
# The two run different compiled programs (padded capacity vs. the
# request's own rows), so matmul reduction order may differ by an ulp.
# Under int8_topk such an ulp can move one feature across an int8 step
# (1/127 of the row's abs-max), hence the looser bound.
SCORE_ATOL = {"none": 1e-4, "int8_topk": 1e-2}
# The (4, 1) mesh sums over clients in another order than one device.
MESH_LOSS_RTOL = 1e-3

_COLLECTIVES = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\(")


@dataclasses.dataclass(frozen=True)
class Sizes:
    clients: int = 16
    rows_cap: int = 256
    n_train: int = 65536
    n_val: int = 2048
    d_hidden: int = 256
    n_layers: int = 2
    n_sampled: int = 8  # phase (c)
    requests: int = 32  # per serving mix
    rows: int = 8  # rows per request vary 1..rows


FULL = Sizes()


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def train_args(sz: Sizes, *flags: str) -> argparse.Namespace:
    """The trainer's own CLI, at these sizes plus ``flags``."""
    return tf.build_parser().parse_args([
        "--task", TASK, "--clients", str(sz.clients),
        "--rows-cap", str(sz.rows_cap), "--n-train", str(sz.n_train),
        "--n-val", str(sz.n_val), "--d-hidden", str(sz.d_hidden),
        "--n-layers", str(sz.n_layers), *flags])


def check_losses(history: list[dict], where: str) -> None:
    for row in history:
        for k, v in row.items():
            if k.startswith("loss"):
                check(np.isfinite(v), f"{where}: round {row['round']} {k}={v}")


def collectives(hlo: str) -> dict:
    return dict(Counter(m.group(1) for m in _COLLECTIVES.finditer(hlo)))


def timed_run(args, spec, batcher, round_fn, state, log) -> tuple:
    """``train_federated.run`` from round 0; returns the history and the
    mean s/round over rounds 2-3 (round 1 compiles), taken at the log
    line that ends each round."""
    ends = []

    def log_round(msg: str) -> None:
        if msg.startswith("round"):
            ends.append(time.perf_counter())
        log(msg)

    history = tf.run(args, spec, batcher, round_fn, 0, state, log=log_round)
    return history, (ends[2] - ends[0]) / 2


def compiled_hlo(batcher, round_fn, state) -> str:
    """Text of the round program as compiled for round 0's batch."""
    batch = batcher.put(batcher.build(0, None))
    return round_fn.lower(state, batch).compile().as_text()


def phase_device(chips: int) -> dict:
    """(a) The run is on the chip, or it is not run at all."""
    info = device_info()
    print(f"(a) device kind {info['kind']!r}, count {info['count']}, "
          f"platform {info['platform']}", flush=True)
    check(info["platform"] == "tpu",
          f"no TPU: JAX reports platform {info['platform']!r}")
    check(info["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX reports "
          f"{info['count']}")
    return info


def phase_train(sz: Sizes, ckpt_dir: str, log=print) -> list[dict]:
    """(b) ``build_federation -> init_or_restore -> run``, 3 rounds,
    checkpointed at round 3."""
    args = train_args(sz, "--rounds", "3", "--ckpt-dir", ckpt_dir,
                      "--ckpt-every", "3")
    spec, batcher, round_fn, mesh = tf.build_federation(args)
    start, state = tf.init_or_restore(args, spec, mesh)
    check(start == 0, f"fresh checkpoint dir restored round {start}")
    history, s_round = timed_run(args, spec, batcher, round_fn, state, log)
    check_losses(history, "(b)")
    first, last = history[0]["loss_uni"], history[-1]["loss_uni"]
    check(last < first, f"(b) loss_uni did not fall: {first} -> {last}")
    n = int(round_fn._cache_size())
    check(n == 1, f"(b) the round compiled {n} times")
    log(f"(b) ok: loss_uni {first:.4f} -> {last:.4f}, round cache {n}, "
        f"{s_round!r} s/round over rounds 2-3 (smoke figure, not a "
        "benchmark)")
    return history


def phase_codec(sz: Sizes, log=print) -> list[dict]:
    """(c) The (b) federation under int8_topk, K-of-C sampled, 2 rounds."""
    args = train_args(sz, "--rounds", "2", "--codec", "int8_topk",
                      "--n-sampled", str(sz.n_sampled))
    spec, batcher, round_fn, mesh = tf.build_federation(args)
    _, state = tf.init_or_restore(args, spec, mesh)
    history = tf.run(args, spec, batcher, round_fn, 0, state, log=log)
    check_losses(history, "(c)")
    compiled = "tpu_custom_call" in compiled_hlo(batcher, round_fn, state)
    # on the chip the kernel must be compiled into the round; on the CPU
    # (tests only) it runs interpreted and leaves no custom call
    check(compiled == kernels.on_tpu(),
          f"(c) tpu_custom_call in the compiled round: {compiled}, "
          f"on_tpu: {kernels.on_tpu()}")
    log(f"(c) ok: finite losses, wire-codec kernel compiled into the "
        f"round: {compiled}")
    return history


def phase_serve(sz: Sizes, ckpt_dir: str, log=print) -> dict:
    """(d) Every request mix through the engine, plus vfl_heavy under
    int8_topk. Returns the largest score difference per codec."""
    spec = make_task(TASK)
    ecfg = EncoderConfig(d_hidden=sz.d_hidden, n_layers=sz.n_layers,
                         enc_type="mlp")
    models, gmv = sf.models_from_checkpoint(ckpt_dir, spec, ecfg)
    worst = {}
    for codec, mixes in (("none", sorted(sf.MIXES)),
                         ("int8_topk", ["vfl_heavy"])):
        engine = sf.build_engine(
            argparse.Namespace(capacities="2,4,16,64", codec=codec,
                               window=32, prefetch=2),
            models, gmv, ecfg, spec.kind)
        analytic, diff = 0, 0.0
        for mix in mixes:
            reqs = sf.make_requests(spec, mix, sz.requests, rows=sz.rows,
                                    seed=0)
            results = engine.run(reqs)
            check([r.index for r in results] == list(range(len(reqs))),
                  f"(d) {mix}: results out of order")
            for res, req in zip(results, reqs):
                ref = predict(models, req, ecfg, spec.kind, server_gmv=gmv,
                              codec=codec if req.vfl else None)
                check(res.route is ref.route,
                      f"(d) {mix}: route {res.route} vs predict {ref.route}")
                got, want = np.asarray(res.scores), np.asarray(ref.scores)
                check(got.shape == want.shape and np.isfinite(got).all(),
                      f"(d) {mix} request {res.index}: bad scores")
                diff = max(diff, float(np.abs(got - want).max()))
                if req.vfl:
                    analytic += communication_cost(
                        request_rows(req), ecfg.d_hidden, "vfl", spec.out_dim,
                        codec=codec)["bytes"]
            log(f"(d) {codec} {mix}: {len(reqs)} requests served")
        caches = engine.cache_counts()
        check(bool(caches) and all(v == 1 for v in caches.values()),
              f"(d) {codec}: compile cache not 1 per (route, capacity): "
              f"{caches}")
        measured = engine.stats["wire_bytes"]
        check(measured == analytic,
              f"(d) {codec}: measured wire bytes {measured} != analytic "
              f"{analytic}")
        check(diff <= SCORE_ATOL[codec],
              f"(d) {codec}: scores differ from predict by {diff} > "
              f"{SCORE_ATOL[codec]}")
        worst[codec] = diff
        log(f"(d) ok {codec}: {len(caches)} programs, cache 1 each; wire "
            f"bytes {measured} = analytic; max |score - predict| {diff!r} "
            f"(limit {SCORE_ATOL[codec]})")
    return worst


def phase_inhost(sz: Sizes, log=print) -> list[dict]:
    """(e) The in-host Federation that ``serve_federated`` trains
    through, whose blend runs the blendavg kernel, for 2 rounds."""
    spec = make_task(TASK)
    ecfg = EncoderConfig(d_hidden=sz.d_hidden, n_layers=sz.n_layers,
                         enc_type="mlp")
    _, _, history = sf.train_models(spec, ecfg, rounds=2, clients=sz.clients,
                                    seed=0)
    check_losses(history, "(e)")
    log("(e) ok: " + "; ".join(
        f"round {h['round']} " + " ".join(
            f"{k} {v:.4f}" for k, v in h.items() if k.startswith("loss"))
        for h in history))
    return history


def _mesh_run(sz: Sizes, devices, log=print) -> tuple[list[dict], str]:
    args = train_args(sz, "--rounds", "3")
    spec, batcher, round_fn, mesh = tf.build_federation(args, devices=devices)
    _, state = tf.init_or_restore(args, spec, mesh)
    history, s_round = timed_run(args, spec, batcher, round_fn, state, log)
    n = int(round_fn._cache_size())
    check(n == 1, f"{len(devices)}-device mesh: the round compiled {n} times")
    log(f"{len(devices)}-device mesh: round cache {n}, {s_round!r} s/round "
        "over rounds 2-3 (smoke figure, not a benchmark)")
    return history, compiled_hlo(batcher, round_fn, state)


def phase_four_chips(sz: Sizes, devices, log=print) -> dict:
    """The (b) federation on a (4, 1) ("data", "model") mesh, then on a
    one-device mesh in the same process: the loss trajectories agree."""
    hist4, hlo4 = _mesh_run(sz, devices[:4], log)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:4]]
    hist1, _ = _mesh_run(sz, devices[:1], log)
    check_losses(hist4, "4-chip")
    check_losses(hist1, "1-device")
    worst = 0.0
    for r4, r1 in zip(hist4, hist1):
        for k in r4:
            if k.startswith("loss"):
                rel = abs(r4[k] - r1[k]) / max(abs(r1[k]), 1e-12)
                worst = max(worst, rel)
                check(rel <= MESH_LOSS_RTOL,
                      f"4-chip vs 1-device: round {r4['round']} {k} "
                      f"{r4[k]!r} vs {r1[k]!r}")
    colls = collectives(hlo4)
    log(f"4-chip ok: losses agree with one device, worst relative "
        f"difference {worst!r} (limit {MESH_LOSS_RTOL}); peak bytes per "
        f"device {peaks}; collectives in the compiled round {colls}")
    return {"worst_rel": worst, "peaks": peaks, "collectives": colls}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    use_compile_cache()
    info = phase_device(args.chips)
    t0 = time.perf_counter()

    def log(msg: str) -> None:
        print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)

    if args.chips == 4:
        phase_four_chips(FULL, jax.devices(), log)
    else:
        with tempfile.TemporaryDirectory() as ckpt_dir:
            phase_train(FULL, ckpt_dir, log)
            phase_codec(FULL, log)
            phase_serve(FULL, ckpt_dir, log)
        phase_inhost(FULL, log)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()

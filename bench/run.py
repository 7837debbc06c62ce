"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the cell's per-layer metrics, the device's busy and window seconds, and a
breakdown of device time and idle gaps. The run fails, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    from bench import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    info = common.resolve(args.workload)
    cfg, traffic = info["config"], info["traffic"]
    common.encoder(cfg)  # a configuration's encoder without a module fails here
    import jax

    common.use_compile_cache()
    try:
        devs = common.devices(info["cell"]["chips"])
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", file=sys.stderr)
    peaks = common.peaks_for(devs[0].device_kind)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    if traffic["kind"] == "train":
        from bench.train_cell import drive
    elif traffic["kind"] == "serve":
        from bench.serve_cell import drive
    else:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    out = drive(cfg, traffic, args.seed, args.seconds, bool(args.trace), devs,
                common.Clock(T_START), common.CACHE)
    run = out["run"]
    print(f"bench: {out['attempted']} attempted, {out['failed']} failed, "
          f"window {run['window_s']:.3f} s, set-up {run['setup_s']:.3f} s, "
          f"{run['compiles_in_window']} compiles in the window, check "
          f"{run['check_s']:.3f} s",
          file=sys.stderr)
    print("bench: host-clock readings " + json.dumps(out["e2e"]), file=sys.stderr)
    for key in ("round_ms", "probe", "trace_read_s", "op_scopes_s",
                "op_scopes_pct", "grad_worst"):
        if key in run:
            print(f"bench: {key} " + json.dumps(run[key]), file=sys.stderr)
    correct, compared = common.compare(out["readings"], info["limits"])
    device = out["device"]
    if args.trace:
        summary = run["trace"]
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        ctx = types.SimpleNamespace(cfg=cfg, traffic=traffic, peaks=peaks,
                                    **run)
        metrics = {}
        for m in info["per_layer"]:
            v = common.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]], "unit": m["unit"]}
                   for m in info["end_to_end"]}
        breakdown = None
    result = {"correct": bool(correct and out["failed"] == 0),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    common.emit(result)
    return 0


if __name__ == "__main__":
    sys.path[:] = [str(_ROOT), str(_ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != _ROOT / "bench"]
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)

"""Readings that set a cell's limits, and a serving cell's knee.

Run on the chip, one process per call:

    python bench/calibrate.py readings --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 3]
    python bench/calibrate.py knee --workload <cell> --rates 100,200,400

``readings`` runs the cell's own set-up, a short window and the check for
each seed (the program's readings), then the reference computed in the
next precision below the configuration's (``high``: three bfloat16
passes) in the program's place on the ``--control-seeds`` (the
control's readings) and, for training cells, the reference with half of
each slab's rows left out (the half-batch fault). Every row of readings
is passed through the harness's own comparison with the cell's limits
(``common.compare``) and carries its ``correct`` flag: the program's has
to be true, the control's and the faults' false. ``knee`` offers a
serving cell's schedule at each rate and reports whether completions kept
up. Results go to stdout, one JSON line each.
"""
import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]


def readings(info, devs, seeds, control_seeds, seconds):
    from bench import checks, common, serve_cell, traffic_gen, train_cell

    cfg, traffic = info["config"], info["traffic"]

    def emit(row):
        row["correct"] = common.compare(row, info["limits"])[0]
        print(json.dumps(row), flush=True)

    for seed in seeds:
        drive = (train_cell if traffic["kind"] == "train" else serve_cell).drive
        out = drive(cfg, traffic, seed, seconds, False, devs, common.Clock(),
                    common.CACHE)
        row = {"seed": seed, "who": "program", **out["readings"],
               "setup_s": out["run"]["setup_s"], "check_s": out["run"]["check_s"],
               **out["e2e"]}
        if "grad_worst" in out["run"]:
            row["grad_worst"] = out["run"]["grad_worst"]
        emit(row)
    for seed in control_seeds:
        if traffic["kind"] == "train":
            store_dir = train_cell.datastore.ensure(cfg, common.CACHE)
            n = traffic["setup_rounds"]
            ref = train_cell.reference_readings(cfg, store_dir, seed, n)
            for who, kw in (("control_high", {"precision": "high"}),
                            ("fault_half_batch", {"rows_frac": 0.5})):
                got = train_cell.reference_readings(cfg, store_dir, seed, n, **kw)
                emit({"seed": seed, "who": who,
                      **checks.train_readings(got, ref),
                      "grad_worst": checks.worst_grad_leaves(got, ref)})
        else:
            store_dir = train_cell.datastore.ensure(cfg, common.CACHE)
            traffic = serve_cell.resolve_traffic(traffic, store_dir)
            pool = train_cell.refdata.StoreFiles(store_dir).val()
            sched = traffic_gen.schedule(traffic, seed, seconds,
                                         len(pool["val_y"]))
            picks = traffic_gen.check_sample(sched, seed,
                                             traffic["check_requests"])
            ref = serve_cell.reference_scores(cfg, traffic, pool, sched, picks,
                                              seed)
            for who, prec in (("control_high", "high"), ("control_bf16", "bf16")):
                got = serve_cell.reference_scores(cfg, traffic, pool, sched,
                                                  picks, seed, precision=prec)
                emit({"seed": seed, "who": who,
                      **checks.serve_readings(got, ref)})


def knee(info, devs, rates, seconds):
    import numpy as np

    from bench import common, serve_cell, traffic_gen

    cfg = info["config"]
    pool, engine, traffic = serve_cell.setup(cfg, info["traffic"], 1, devs,
                                             common.CACHE)
    for rate in rates:
        sched = traffic_gen.schedule(dict(traffic, rate_per_s=rate), 7, seconds,
                                     len(pool["val_y"]))
        t0, issued, done, _ = serve_cell.serve_window(engine, pool, sched, ())
        lat = done - (t0 + sched["due"])
        q = len(lat) // 4
        row = {"rate": rate, "requests": len(lat),
               "completed_per_s": float(len(lat) / (np.nanmax(done) - t0)),
               "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
               "p95_ms": float(np.nanpercentile(lat, 95) * 1e3),
               "first_quarter_p50_ms": float(np.nanmedian(lat[:q]) * 1e3),
               "last_quarter_p50_ms": float(np.nanmedian(lat[-q:]) * 1e3),
               "lag_p95_ms": float(np.percentile(issued - t0 - sched["due"], 95)
                                   * 1e3),
               **serve_cell.probe(t0, sched, issued, done, [])}
        print(json.dumps(row), flush=True)


def main() -> int:
    from bench import common

    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("readings", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3",
                    help="seeds of the program's readings ('' for none)")
    ap.add_argument("--control-seeds", default="1,2,3",
                    help="seeds of the control's and faults' readings")
    ap.add_argument("--rates", default="100,200,400")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    info = common.resolve(args.workload)
    import jax

    common.use_compile_cache()
    devs = common.devices(info["cell"]["chips"])
    jax.config.update("jax_default_matmul_precision",
                      info["config"]["matmul_precision"])
    if args.mode == "readings":
        seeds = lambda t: [int(x) for x in t.split(",") if x]  # noqa: E731
        readings(info, devs, seeds(args.seeds), seeds(args.control_seeds),
                 args.seconds)
    else:
        knee(info, devs, [float(r) for r in args.rates.split(",")],
             args.seconds)
    return 0


if __name__ == "__main__":
    sys.path[:] = [str(_ROOT), str(_ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != _ROOT / "bench"]
    sys.exit(main())

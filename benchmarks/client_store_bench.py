"""§Client store: peak host RSS stays flat as the dataset outgrows RAM.

The out-of-core claim of ``repro.data.store.ClientStore``: a store-backed
``FederatedBatcher`` materializes only the drawn row subsets per round —
O(K*N*row_bytes) — so a federation's peak host RSS is independent of the
TOTAL dataset size, while the in-memory loader's RSS grows linearly with
it.

Protocol: for each total-rows scale in {1x, 2x, 4x} (K*N, C, and the
model held fixed) this driver

  1. imports the synthetic partition into an on-disk store in a throwaway
     subprocess (``repro.launch.train_federated import``), then
  2. runs one measuring subprocess per (mode, scale): ``--child`` builds
     the federation (mode ``inmem`` generates + holds the arrays in RAM;
     mode ``store`` opens the store) and drives real rounds through the
     jitted sharded round, reporting its own lifetime
     ``resource.getrusage`` high-water mark.

Fresh processes are the only honest way to compare RSS high-water marks:
``ru_maxrss`` never decreases, so measuring both modes (or two scales) in
one process would let the largest configuration mask all the others.

Acceptance (recorded, then asserted — the JSON always lands):
``store_rss_growth`` (max-scale RSS / 1x RSS, store mode) stays ~flat
(< 1.25) while ``inmem_rss_growth`` grows with the data; batches remain
bit-identical between the two modes by construction (see
``tests/test_store.py``).

    PYTHONPATH=src python -m benchmarks.client_store_bench [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_MARK = "@@CLIENT_STORE_RESULT "


# ------------------------------------------------------------------ child --

def _child(mode: str, scale: int, args) -> None:
    """One measuring process: build the federation, run rounds, report
    lifetime max RSS. Printed as a marked JSON line for the parent."""
    import argparse as _ap

    import jax

    from benchmarks.emit import max_rss_mb
    from repro.launch.train_federated import build_federation, place_state
    from repro.core.federation_sharded import init_round_state

    ns = _ap.Namespace(
        task="smnist", clients=args.clients, n_sampled=0,
        n_train=args.base_rows * scale, n_val=256, rows_cap=args.rows_cap,
        d_hidden=32, n_layers=1, lr=1e-2, optimizer="adamw",
        dirichlet_alpha=None, seed=0, data_seed=0, prefetch=1,
        store_dir=args.store_dir if mode == "store" else None)
    spec, batcher, round_fn, mesh = build_federation(ns)
    state = place_state(init_round_state(jax.random.PRNGKey(0), spec), mesh)
    # warmup round compiles; timed rounds then measure steady state
    for _, batch in batcher.rounds(0, 1, prefetch=0):
        state, _ = round_fn(state, batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _, batch in batcher.rounds(1, 1 + args.rounds):
        state, _ = round_fn(state, batch)
    jax.block_until_ready(state)
    rec = {
        "mode": mode, "scale": scale, "backend": jax.default_backend(),
        "total_rows": ns.n_train,
        "max_rss_mb": round(max_rss_mb(), 1),
        "s_per_round": round((time.perf_counter() - t0) / args.rounds, 4),
        "compile_cache": int(round_fn._cache_size()),
    }
    print(_MARK + json.dumps(rec), flush=True)


# ----------------------------------------------------------------- parent --

def _spawn(argv: list[str]) -> str:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, *argv], env=env, cwd=root,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"child {argv} failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


def _run_child(mode: str, scale: int, args) -> dict:
    out = _spawn(["-m", "benchmarks.client_store_bench", "--child",
                  "--mode", mode, "--scale", str(scale),
                  "--store-dir", args.store_dir or "",
                  "--clients", str(args.clients),
                  "--base-rows", str(args.base_rows),
                  "--rows-cap", str(args.rows_cap),
                  "--rounds", str(args.rounds)])
    for line in out.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(f"no result line in child output:\n{out}")


def main(quick: bool = False, args=None) -> None:
    # the parent never imports JAX: the children are the processes that
    # hold the device, one at a time
    from benchmarks.emit import write_bench_json

    # CLI overrides win; unset fields fall back to quick-aware defaults
    defaults = dict(clients=8 if quick else 16,
                    base_rows=4096 if quick else 16384,
                    rows_cap=32, rounds=2 if quick else 3, store_dir=None)
    if args is None:
        args = argparse.Namespace(**defaults)
    for k, v in defaults.items():
        if getattr(args, k, None) is None:
            setattr(args, k, v)
    scales = (1, 2) if quick else (1, 2, 4)
    print("\n=== client store: flat RSS as total rows grow "
          f"{scales[-1]}x (C={args.clients}, K*N fixed) ===")

    records = []
    with tempfile.TemporaryDirectory(prefix="client_store_bench_") as tmp:
        for scale in scales:
            store_dir = os.path.join(tmp, f"store_{scale}x")
            # import in a throwaway process: the converter materializes
            # the full dataset, which must not pollute any measurement
            _spawn(["-m", "repro.launch.train_federated", "import",
                    "--store-dir", store_dir,
                    "--clients", str(args.clients),
                    "--n-train", str(args.base_rows * scale),
                    "--n-val", "256"])
            for mode in ("inmem", "store"):
                cargs = argparse.Namespace(**{**vars(args),
                                              "store_dir": store_dir})
                records.append(_run_child(mode, scale, cargs))
                r = records[-1]
                print(f"{r['mode']:>6s} {r['scale']}x rows={r['total_rows']:6d} "
                      f"maxrss {r['max_rss_mb']:7.1f} MiB  "
                      f"{r['s_per_round']:.3f}s/round  cache {r['compile_cache']}")

    def _growth(mode: str) -> float:
        rss = {r["scale"]: r["max_rss_mb"] for r in records if r["mode"] == mode}
        return round(rss[scales[-1]] / rss[scales[0]], 3)

    summary = {"store_rss_growth": _growth("store"),
               "inmem_rss_growth": _growth("inmem"),
               "scales": list(scales)}
    print(f"--> RSS growth {scales[0]}x -> {scales[-1]}x: "
          f"store {summary['store_rss_growth']}x, "
          f"inmem {summary['inmem_rss_growth']}x")
    # emit before asserting: a failed acceptance still leaves evidence
    write_bench_json("BENCH_client_store.json",
                     {"bench": "client_store",
                      "backend": records[0]["backend"],
                      "n_clients": args.clients, "rows_cap": args.rows_cap,
                      "records": records, "summary": summary})
    assert all(r["compile_cache"] == 1 for r in records), \
        "store-backed rounds must reuse the one compiled program"
    if summary["store_rss_growth"] > 1.25:
        print(f"WARNING: store-backed RSS grew {summary['store_rss_growth']}x "
              f"across a {scales[-1]}x dataset (target ~flat, < 1.25x)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--mode", choices=["inmem", "store"])
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--store-dir", default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--base-rows", type=int, default=None)
    ap.add_argument("--rows-cap", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    cli = ap.parse_args()
    if cli.child:
        _child(cli.mode, cli.scale, cli)
    else:
        main(quick=cli.quick, args=cli)

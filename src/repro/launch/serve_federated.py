"""Serving driver for the trained federation: blended models behind the
micro-batched request engine.

    # serve 3 request mixes off a tiny in-process training run
    PYTHONPATH=src python -m repro.launch.serve_federated --train-rounds 6 \
        --requests 64 --mix all_multimodal --mix mixed_unimodal --mix vfl_heavy

    # serve from a train_federated checkpoint (blended global models +
    # the VFL server head restored straight out of the round state)
    PYTHONPATH=src python -m repro.launch.serve_federated \
        --ckpt-dir /tmp/fedckpt --requests 256 --mix vfl_heavy

    # CI smoke: 2 mixes through one engine, cache + parity assertions
    PYTHONPATH=src python -m repro.launch.serve_federated --selftest

The engine (``repro.core.serving``) is the paper's decentralized-
inference pillar at serving scale: requests route by available
modalities to the blended local heads, pad into capacity-bucketed
micro-batches (one compiled program per (route, capacity), cache 1
forever), and the VFL fallback's feature/score messages meter real wire
bytes through the codec. The LM prefill/decode demo that used to own
the ``serve`` name lives at ``repro.launch.serve_lm``.
"""
from __future__ import annotations

import argparse
import time
import zlib

import numpy as np


# Request-mix presets: probability of (multimodal, A-only, B-only, vfl).
MIXES = {
    "all_multimodal": (1.0, 0.0, 0.0, 0.0),
    "mixed_unimodal": (0.0, 0.5, 0.5, 0.0),
    "vfl_heavy": (0.2, 0.1, 0.1, 0.6),
}


def models_from_checkpoint(ckpt_dir: str, spec, ecfg, step: int | None = None):
    """Blended ``global_models`` + VFL ``server_gmv`` out of a
    ``train_federated`` round-state checkpoint.

    Restores through a partial template (just the two serving blocks —
    the stacked per-client models, optimizer moments, and telemetry
    stay on disk), after a manifest preflight that checks the requested
    ``--d-hidden`` against the checkpoint's actual head shapes so a
    mismatch fails with dims, not a leaf-by-leaf shape error.
    """
    import jax

    from repro.checkpoint import latest_step, read_manifest, restore_checkpoint
    from repro.core.encoders import fusion_init, init_client_models

    resolved = latest_step(ckpt_dir) if step is None else step
    if resolved is None:
        raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    manifest = read_manifest(ckpt_dir, resolved)
    try:
        d_ck, out_ck = manifest["shapes"]["server_gmv/out/w"]
    except KeyError:
        raise KeyError(f"checkpoint {ckpt_dir} step {resolved} has no "
                       "server_gmv head — not a round-state checkpoint")
    if (d_ck, out_ck) != (ecfg.d_hidden, spec.out_dim):
        raise ValueError(
            f"checkpoint {ckpt_dir} step {resolved} was trained with "
            f"d_hidden={d_ck}, out_dim={out_ck}; this serving config asks "
            f"for d_hidden={ecfg.d_hidden}, out_dim={spec.out_dim} — fix "
            "--d-hidden/--task to match (see tools/ckpt_inspect.py)")
    template = {
        "global_models": init_client_models(jax.random.PRNGKey(0), spec, ecfg),
        "server_gmv": fusion_init(jax.random.PRNGKey(0), ecfg.d_hidden,
                                  spec.out_dim),
    }
    state = restore_checkpoint(ckpt_dir, template, step=resolved)
    print(f"restored blended models from {ckpt_dir} step {resolved}")
    return state["global_models"], state["server_gmv"]


def train_models(spec, ecfg, *, rounds: int, clients: int, seed: int):
    """Tiny in-process BlendFL federation — enough training that the
    served models are real blended artifacts, not random init. Returns
    ``(global_models, server_gmv, history)``, one log dict per round."""
    import jax

    from repro.core.federation import FedConfig, Federation
    from repro.core.partitioner import partition
    from repro.data.synthetic import train_val_test

    tr, va, _ = train_val_test(spec, 240, 120, 60, seed=seed)
    parts = partition(tr, clients, seed=seed + 1)
    fcfg = FedConfig(n_clients=clients, rounds=rounds, batch_size=32,
                     seed=seed)
    fed = Federation.init(jax.random.PRNGKey(seed), fcfg, spec, ecfg,
                          parts, va)
    history = fed.fit()
    print(f"trained in-process federation: {clients} clients, "
          f"{rounds} rounds")
    return fed.global_models, fed.server_gmv, history


def make_requests(spec, mix: str, n: int, *, rows: int, seed: int) -> list:
    """A deterministic heterogeneous request stream for one mix preset.
    Row counts vary per request (1..rows) so the stream exercises
    multiple capacity buckets and the chunking path."""
    from repro.core.inference import InferenceRequest

    p_mm, p_a, p_b, p_vfl = MIXES[mix]
    # zlib.crc32: the same stream in every process (hash() is salted)
    rng = np.random.default_rng([seed, zlib.crc32(mix.encode()) & 0xFFFF])
    kinds = rng.choice(4, size=n, p=[p_mm, p_a, p_b, p_vfl])
    out = []
    for kind in kinds:
        m = int(rng.integers(1, rows + 1))
        xa = rng.standard_normal((m, spec.seq_a, spec.feat_a)).astype(np.float32)
        xb = rng.standard_normal((m, spec.seq_b, spec.feat_b)).astype(np.float32)
        if kind == 1:
            out.append(InferenceRequest(xa, None))
        elif kind == 2:
            out.append(InferenceRequest(None, xb))
        else:
            out.append(InferenceRequest(xa, xb, vfl=(kind == 3)))
    return out


def serve_mix(engine, spec, mix: str, n: int, *, rows: int, seed: int) -> dict:
    """Run one mix through the engine; per-mix latency/throughput/bytes."""
    reqs = make_requests(spec, mix, n, rows=rows, seed=seed)
    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    lat_ms = np.array([r.latency_s for r in results]) * 1e3
    total_rows = sum(len(r.scores) for r in results)
    return {
        "mix": mix, "requests": n, "rows": total_rows,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "rps": n / wall, "rows_per_s": total_rows / wall,
        "bytes_per_request": sum(r.bytes for r in results) / n,
        "wall_s": wall,
        "results": results,
    }


def build_engine(args, models, server_gmv, ecfg, kind):
    from repro.core.serving import ServingConfig, ServingEngine

    cfg = ServingConfig(
        capacities=tuple(int(c) for c in args.capacities.split(",")),
        codec=args.codec, window=args.window, prefetch=args.prefetch)
    return ServingEngine(models, ecfg, kind, server_gmv=server_gmv, cfg=cfg)


def selftest(args) -> None:
    """Smoke assertion for CI: two different request mixes through ONE
    engine must (a) keep the compile cache at exactly 1 per (route,
    capacity), (b) score every request bit-identically to a
    single-request ``predict`` call, and (c) meter wire bytes that
    reconcile exactly with the analytic ``communication_cost``."""
    from repro.core.inference import predict
    from repro.data.synthetic import make_task
    from repro.core.encoders import EncoderConfig

    spec = make_task(args.task)
    ecfg = EncoderConfig(d_hidden=args.d_hidden, n_layers=args.n_layers,
                         enc_type=args.enc_type)
    models, gmv, _ = train_models(spec, ecfg,
                                  rounds=max(2, args.train_rounds),
                                  clients=args.clients, seed=args.seed)
    engine = build_engine(args, models, gmv, ecfg, spec.kind)

    total_bytes = 0
    for mix in ("mixed_unimodal", "vfl_heavy"):
        reqs = make_requests(spec, mix, args.requests, rows=args.rows,
                             seed=args.seed)
        results = engine.run(reqs)
        assert [r.index for r in results] == list(range(len(reqs)))
        for res, req in zip(results, reqs):
            ref = predict(models, req, ecfg, spec.kind, server_gmv=gmv,
                          codec=args.codec if req.vfl else None)
            assert res.route is ref.route, (res.route, ref.route)
            assert np.array_equal(np.asarray(res.scores),
                                  np.asarray(ref.scores)), \
                f"padded-batch scores diverge from predict ({mix}, " \
                f"request {res.index}, route {res.route.value})"
        total_bytes += sum(r.bytes for r in results)
        print(f"selftest mix {mix}: {len(reqs)} requests bit-exact vs "
              "predict")
    caches = engine.cache_counts()
    assert caches and all(v == 1 for v in caches.values()), \
        f"compile cache not 1 per (route, capacity): {caches}"
    assert total_bytes == engine.stats["wire_bytes"], \
        (total_bytes, engine.stats["wire_bytes"])
    print(f"selftest ok: caches {dict(caches)}; measured wire bytes "
          f"{engine.stats['wire_bytes']} reconcile with analytic")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="serve a trained federation's blended models")
    ap.add_argument("--task", default="smnist")
    ap.add_argument("--ckpt-dir", default=None,
                    help="train_federated checkpoint to serve from "
                         "(default: train a tiny federation in-process)")
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--train-rounds", type=int, default=6)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--d-hidden", type=int, default=32)
    ap.add_argument("--n-layers", type=int, default=1)
    ap.add_argument("--enc-type", default="mlp",
                    choices=("mlp", "recurrent", "transformer"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=64,
                    help="requests per mix")
    ap.add_argument("--rows", type=int, default=8,
                    help="max rows per request (row counts vary 1..rows)")
    ap.add_argument("--mix", action="append", default=None,
                    choices=sorted(MIXES), help="request mix preset "
                    "(repeatable; default: all three)")
    ap.add_argument("--capacities", default="2,4,16,64")
    ap.add_argument("--codec", default="none",
                    help="wire codec for the VFL fallback route")
    ap.add_argument("--window", type=int, default=32)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--selftest", action="store_true",
                    help="2 mixes + cache/parity/bytes assertions, then exit")
    args = ap.parse_args()

    from repro.launch.runtime import device_line, use_compile_cache

    use_compile_cache()
    print(device_line())
    if args.selftest:
        selftest(args)
        return

    from repro.core.encoders import EncoderConfig
    from repro.data.synthetic import make_task

    spec = make_task(args.task)
    ecfg = EncoderConfig(d_hidden=args.d_hidden, n_layers=args.n_layers,
                         enc_type=args.enc_type)
    if args.ckpt_dir:
        models, gmv = models_from_checkpoint(args.ckpt_dir, spec, ecfg,
                                             step=args.step)
    else:
        models, gmv, _ = train_models(spec, ecfg, rounds=args.train_rounds,
                                      clients=args.clients, seed=args.seed)
    engine = build_engine(args, models, gmv, ecfg, spec.kind)

    for mix in (args.mix or sorted(MIXES)):
        row = serve_mix(engine, spec, mix, args.requests, rows=args.rows,
                        seed=args.seed)
        print(f"mix {mix:>15}: {row['requests']} req ({row['rows']} rows) "
              f"p50 {row['p50_ms']:.2f}ms p99 {row['p99_ms']:.2f}ms "
              f"{row['rps']:.1f} req/s {row['bytes_per_request']:.0f} B/req")
    st = engine.stats
    print(f"engine: {st['batches']} batches over routes "
          f"{ {k: v for k, v in st['batches_by_route'].items() if v} }; "
          f"wire {st['wire_messages']} msgs / {st['wire_bytes']} bytes; "
          f"build {st['build_seconds']:.3f}s stall {st['stall_seconds']:.3f}s "
          f"execute {st['execute_seconds']:.3f}s")
    print(f"compile caches (must all be 1): {engine.cache_counts()}")


if __name__ == "__main__":
    main()

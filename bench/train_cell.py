"""A training cell: the SPMD round driven by ``train_federated.run`` over
the store-backed ``FederatedBatcher``, exactly as the trainer drives it.

Set-up builds the federation through ``build_federation``'s store branch,
makes the round state from the seed in one jitted call, and hands both to
``run``, which compiles the round and drives the first ``setup_rounds``
rounds (the rounds the reference follows). The window is the same ``run``
call going on: it opens when those rounds are done and closes at the first
round that ends ``--seconds`` after it opened. A traced run then maps each
device op of the window to its named scope (``op_scopes``) for the readers.
"""
from __future__ import annotations

import argparse
import gc
import shutil
import tempfile
import threading
import time

import numpy as np

from bench import checks, datastore, refdata, reference, traces
from bench.common import annotate, device_info, encoder_args
from bench.flops import train_round


class WindowClosed(Exception):
    pass


def _args(cfg: dict, traffic: dict, store_dir: str, seed: int):
    return argparse.Namespace(
        store_dir=store_dir, scenario=None, rows_cap=cfg["rows_cap"],
        lr=cfg["lr"], optimizer=cfg["optimizer"], n_sampled=traffic["n_sampled"],
        policy=traffic["policy"], codec=traffic["codec"],
        topk_frac=traffic["topk_frac"], strategy=traffic["strategy"],
        fedprox_mu=0.0, server_opt="none", server_lr=1.0, n_malicious=1,
        seed=seed, prefetch=traffic["prefetch"], rounds=1 << 30,
        ckpt_dir=None, ckpt_every=0, log_every=1, **encoder_args(cfg))


def setup(cfg: dict, traffic: dict, seed: int, devs: list, cache_dir):
    """(store_dir, spec, batcher, round_fn, state)."""
    import jax
    from repro.core.federation_sharded import init_round_state
    from repro.launch import train_federated as tf

    store_dir = datastore.ensure(cfg, cache_dir)
    spec, batcher, round_fn, mesh = tf.build_federation(
        _args(cfg, traffic, store_dir, seed), devices=devs)
    for key, want in encoder_args(cfg).items():
        if getattr(spec.ecfg, key) != want:
            raise ValueError(
                f"the configuration sets the encoder's {key} to {want!r}, "
                f"the program built {getattr(spec.ecfg, key)!r}")
    state = tf.place_state(
        jax.jit(init_round_state, static_argnums=1)(reference.seed_key(seed),
                                                     spec), mesh)
    return store_dir, spec, batcher, round_fn, state


def op_scopes(round_fn, args, labels) -> dict:
    """``traces.op_scopes`` of the ops ``labels`` names, from the text of
    ``round_fn`` compiled afresh at ``args``' shapes. The persistent compile
    cache is off for that compile: its key leaves metadata out, so a cached
    executable can carry another build's scopes. A compiler option set to
    its default keeps JAX's in-memory caches from handing back the window's
    executable instead of compiling."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = round_fn.lower(*args).compile({"xla_dump_to": ""}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return traces.op_scopes(text, labels)


def _abstract(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


def program_readings(kept: dict, n: int) -> dict:
    """Losses of rounds 1..n, first moments after round 1, global models
    before round 1 and after round n, as host arrays."""
    import jax

    host = jax.device_get
    return {"losses": [{k: float(host(kept["metrics"][r][k]))
                        for k in ("loss_uni", "loss_vfl", "loss_paired")}
                       for r in range(1, n + 1)],
            "mu1": host({"clients": kept[1]["opt"]["mu"],
                         "server": kept[1]["srv_opt"]["mu"]}),
            "global0": host(kept[0]["global_models"]),
            "global": host(kept[n]["global_models"])}


def _dims(cfg: dict) -> dict:
    return {"cap": cfg["rows_cap"], "seq_a": cfg["seq_a"],
            "feat_a": cfg["feat_a"], "seq_b": cfg["seq_b"],
            "feat_b": cfg["feat_b"], "out_dim": cfg["n_labels"]}


def reference_readings(cfg: dict, store_dir: str, seed: int, n: int,
                       precision: str = "highest", rows_frac: float = 1.0):
    """The reference over rounds 0..n-1, batches rebuilt from the store.
    ``rows_frac`` < 1 keeps only that share of each slab's live rows (the
    half-batch fault)."""
    store = refdata.StoreFiles(store_dir)
    dims = _dims(cfg)
    batches = [refdata.round_batch(store, dims, seed, r) for r in range(n)]
    if rows_frac < 1.0:
        for b in batches:
            cut = int(round(cfg["rows_cap"] * rows_frac))
            for k in ("partial_ma", "partial_mb", "paired_m"):
                b[k][:, cut:] = 0.0
            w = b["frag_w"].reshape(-1, cfg["rows_cap"])
            w[:, cut:] = 0.0
    return reference.train_rounds(cfg, batches, store.val(), seed, precision)


def drive(cfg, traffic, seed, seconds, trace, devs, clock, cache_dir):
    """Set up, run the window, and check. Returns the parts of the result
    line and what the per-layer readers read."""
    import jax

    n_setup = traffic["setup_rounds"]
    store_dir, spec, batcher, round_fn, state = setup(cfg, traffic, seed,
                                                      devs, cache_dir)
    from repro.launch import train_federated as tf

    annotate(batcher, "build", "FederatedBatcher.build")
    annotate(batcher, "put", "FederatedBatcher.put")
    kept, done = {"metrics": {}}, [0]
    compiles = []

    def round_call(st, batch):
        if done[0] == 0:
            kept[0] = st
            if trace:
                win["args"] = jax.tree.map(_abstract, (st, batch))
        with jax.profiler.TraceAnnotation("bench.round_fn"):
            out = round_fn(st, batch)
        done[0] += 1
        if done[0] <= n_setup:
            kept["metrics"][done[0]] = out[1]
            if done[0] in (1, n_setup):
                kept[done[0]] = out[0]
        return out

    win = {}

    def log(_msg):
        n = done[0]
        if n == n_setup:
            win["prog"] = program_readings(kept, n_setup)
            kept.clear()
            if trace:
                win["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
                traces.start(win["dir"])
                win["span"] = jax.profiler.TraceAnnotation(traces.WINDOW_SPAN)
                win["span"].__enter__()
            win["setup_s"] = clock.since()
            win["c0"] = (batcher.build_seconds, batcher.stall_seconds,
                         batcher.rounds_built)
            win["t0"] = time.perf_counter()
        elif n > n_setup:
            win.setdefault("ends", []).append(time.perf_counter())
        if n > n_setup and time.perf_counter() - win["t0"] >= seconds:
            win["t1"] = time.perf_counter()
            win["c1"] = (batcher.build_seconds, batcher.stall_seconds,
                         batcher.rounds_built)
            raise WindowClosed

    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_k: compiles.append(ev)
        if ev.endswith("backend_compile_duration") and "t0" in win
        and "t1" not in win else None)
    try:
        tf.run(_args(cfg, traffic, store_dir, seed), spec, batcher,
               round_call, 0, state, log=log)
    except WindowClosed:
        pass
    del state
    if trace:
        win["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    for t in threading.enumerate():
        if t.name == "federated-batcher-prefetch":
            t.join(timeout=60)
    device = device_info(devs)
    rounds = done[0] - n_setup
    window_s = win["t1"] - win["t0"]
    prog = win["prog"]
    run = {}
    if trace:
        run.update(traces.reduce(win["dir"]))
        shutil.rmtree(win["dir"], ignore_errors=True)
        t_scopes = time.perf_counter()
        evs = [ev for plane in run["ops"].values() for ev in plane]
        run["op_scopes"] = op_scopes(round_fn, win["args"],
                                     [n for n, _, _ in evs])
        run["op_scopes_s"] = time.perf_counter() - t_scopes
        if evs:  # the share of op time that has a scope
            run["op_scopes_pct"] = 100 * sum(
                e - s for n, s, e in evs
                if traces.scope_of(n, run["op_scopes"])) / sum(
                e - s for _, s, e in evs)
    del batcher, round_fn, spec
    gc.collect()
    t_check = time.perf_counter()
    ref = reference_readings(cfg, store_dir, seed, n_setup)
    readings = checks.train_readings(prog, ref)
    grad_worst = checks.worst_grad_leaves(prog, ref)
    check_s = time.perf_counter() - t_check

    run.update(window_s=window_s, rounds=rounds, setup_s=win["setup_s"],
               compiles_in_window=len(compiles), check_s=check_s,
               grad_worst=grad_worst,
               build_s=win["c1"][0] - win["c0"][0],
               stall_s=win["c1"][1] - win["c0"][1],
               built=win["c1"][2] - win["c0"][2],
               round_ms=[round(b * 1e3, 1) for b in
                         np.diff([win["t0"]] + win["ends"])])
    if trace:
        store = refdata.StoreFiles(store_dir)
        dims = _dims(cfg)
        flops = [train_round(cfg, refdata.live_rows(refdata.round_batch(
            store, dims, seed, r, with_x=False)), cfg["n_val"])
            for r in range(n_setup, n_setup + rounds)]
        run["flops_per_round"] = float(np.mean(flops))
    return {"device": device, "readings": readings, "run": run,
            "attempted": rounds, "failed": 0,
            "e2e": {"setup_s": win["setup_s"], "round_s": window_s / rounds}}

"""A serving cell: open-loop arrivals into ``ServingEngine.serve_stream``.

Set-up makes the served models from the seed (one jitted call each for the
client models and the VFL server head), builds the engine with the
traffic file's capacities, window, prefetch and codec, and warms every
program the traffic can reach: each (route, capacity) forward, and each
slice the engine cuts out of a padded batch to hand a request its rows;
then it offers the traffic for ``warm_seconds`` before the window opens.
The window offers the schedule of ``traffic_gen``: the request stream
yields each request at its due time, from the engine's assembly thread,
and a request's latency runs from its due time until ``serve_stream``
yields its result. A traffic file whose ``route_mix`` is ``"store"``
takes the mix from the configuration's data: each request is a patient
routed by the modalities the store holds for such patients
(``datastore.availability``).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time

import numpy as np

from bench import checks, datastore, refdata, reference, traces, traffic_gen
from bench.common import annotate, device_info, encoder_args
from bench.flops import forward_row


def resolve_traffic(traffic: dict, store_dir: str) -> dict:
    """The traffic file with a ``"store"`` route mix replaced by the
    store's modality shares."""
    if traffic["route_mix"] == "store":
        return dict(traffic, route_mix=datastore.availability(store_dir))
    return traffic


def setup(cfg: dict, traffic: dict, seed: int, devs: list, cache_dir):
    """(pool, engine, traffic): the request rows (the store's validation
    rows), the warmed engine and the resolved traffic."""
    import jax
    import jax.numpy as jnp
    from repro.core.encoders import EncoderConfig, fusion_init, init_client_models
    from repro.core.inference import InferenceRequest
    from repro.core.serving import ServingConfig, ServingEngine
    from repro.data.synthetic import TaskSpec

    store_dir = datastore.ensure(cfg, cache_dir)
    traffic = resolve_traffic(traffic, store_dir)
    pool = refdata.StoreFiles(store_dir).val()
    tspec = TaskSpec(cfg["task"], cfg["kind"], cfg["n_labels"], cfg["seq_a"],
                     cfg["feat_a"], cfg["seq_b"], cfg["feat_b"])
    ecfg = EncoderConfig(**encoder_args(cfg))
    k_models, k_server = jax.random.split(reference.seed_key(seed))
    with jax.default_device(devs[0]):
        models = jax.jit(lambda k: init_client_models(k, tspec, ecfg))(k_models)
        server = jax.jit(lambda k: fusion_init(k, cfg["d_hidden"],
                                               cfg["n_labels"]))(k_server)
    caps = tuple(traffic["capacities"])
    engine = ServingEngine(models, ecfg, cfg["kind"], server_gmv=server,
                           cfg=ServingConfig(capacities=caps,
                                             codec=traffic["codec"],
                                             topk_frac=traffic["topk_frac"],
                                             window=traffic["window"],
                                             prefetch=traffic["prefetch"]))
    xa, xb = pool["val_a"], pool["val_b"]
    most = traffic["rows"]["max"]
    for cap in caps:
        engine.run([InferenceRequest(xa[:cap], xb[:cap]),
                    InferenceRequest(xa[:cap], None),
                    InferenceRequest(None, xb[:cap]),
                    InferenceRequest(xa[:cap], xb[:cap], vfl=True)])
        # the slices that hand a request of up to ``most`` rows its scores
        scores = jnp.zeros((cap, cfg["n_labels"]), jnp.float32)
        for start in range(cap):
            for n in range(1, min(cap - start, most) + 1):
                if n < cap:
                    scores[start:start + n].block_until_ready()
    return pool, engine, traffic


def _request(pool, route: str, off: int, rows: int):
    from repro.core.inference import InferenceRequest

    xa = pool["val_a"][off:off + rows]
    xb = pool["val_b"][off:off + rows]
    return InferenceRequest(
        xa if route != "unimodal_B" else None,
        xb if route != "unimodal_A" else None, vfl=route == "vfl_fallback")


def reference_scores(cfg, traffic, pool, sched, picks, seed,
                     precision: str = "highest", block: int = 64) -> dict:
    """{route: (rows, n_labels)} reference scores over the picked requests'
    rows, in pick order, computed ``block`` rows at a time."""
    models, server = reference.serve_models(seed, cfg)
    out = {}
    for ri, route in enumerate(traffic_gen.ROUTES):
        idx = [i for i in picks if sched["route"][i] == ri]
        if not idx:
            out[route] = np.zeros((0, cfg["n_labels"]), np.float32)
            continue
        sl = [np.arange(sched["offset"][i], sched["offset"][i] + sched["rows"][i])
              for i in idx]
        rows = np.concatenate(sl)
        n = len(rows)
        pad = np.concatenate([rows, np.zeros((-n) % block, np.int64)])
        scores = reference.serve_scores(cfg, route, precision,
                                        traffic["topk_frac"])
        parts = []
        for s in range(0, len(pad), block):
            sel = pad[s:s + block]
            parts.append(np.asarray(scores(models, server, pool["val_a"][sel],
                                           pool["val_b"][sel])))
        out[route] = np.concatenate(parts)[:n]
    return out


def program_scores(cfg, sched, picks, results) -> dict:
    out = {}
    for ri, route in enumerate(traffic_gen.ROUTES):
        idx = [i for i in picks if sched["route"][i] == ri]
        out[route] = (np.concatenate([np.asarray(results[i]) for i in idx])
                      if idx else np.zeros((0, cfg["n_labels"]), np.float32))
    return out


def serve_window(engine, pool, sched, keep=None, seconds_lead: float = 0.05):
    """Offer the schedule; return (t0, issued, done, results). Only the
    results of the requests in ``keep`` (all when None) are held."""
    import jax

    n = len(sched["rows"])
    issued = np.zeros(n)
    done = np.full(n, np.nan)
    results = [None] * n
    t0 = time.perf_counter() + seconds_lead
    routes = traffic_gen.ROUTES

    def stream():
        for i in range(n):
            due = t0 + sched["due"][i]
            wait = due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.arrival_wait"):
                    time.sleep(wait)
            issued[i] = time.perf_counter()
            yield _request(pool, routes[sched["route"][i]],
                           int(sched["offset"][i]), int(sched["rows"][i]))

    keep = None if keep is None else set(int(i) for i in keep)
    for res in engine.serve_stream(stream()):
        done[res.index] = time.perf_counter()
        if keep is None or res.index in keep:
            results[res.index] = res.scores
    return t0, issued, done, results


class GcClock:
    """Pauses of Python's cyclic garbage collector while on."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def probe(t0, sched, issued, done, gc_pauses) -> dict:
    """Where a slow window lost its time: the longest stretch without a
    completion, the p95 latency of each fifth of the requests by due time,
    the worst issue lag, and the collector's pauses."""
    due = t0 + sched["due"]
    fin = np.sort(done[~np.isnan(done)])
    gaps = np.diff(np.concatenate([[t0], fin]))
    at = int(np.argmax(gaps))
    fifths = [float(np.nanpercentile((done - due)[part], 95) * 1e3)
              for part in np.array_split(np.arange(len(due)), 5)]
    full = [p for g, p in gc_pauses if g == 2]
    return {"completion_gap_max_ms": float(gaps[at] * 1e3),
            "completion_gap_at_s": float(fin[at] - t0),
            "p95_ms_by_fifth": fifths,
            "issue_lag_max_ms": float(np.max(issued - due) * 1e3),
            "gc_pauses": len(gc_pauses),
            "gc_pause_max_ms": float(max((p for _, p in gc_pauses), default=0)
                                     * 1e3),
            "gc_full_s": float(sum(full))}


def drive(cfg, traffic, seed, seconds, trace, devs, clock, cache_dir):
    import jax

    pool, engine, traffic = setup(cfg, traffic, seed, devs, cache_dir)
    sched = traffic_gen.schedule(traffic, seed, seconds, len(pool["val_y"]))
    picks = traffic_gen.check_sample(sched, seed, traffic["check_requests"])
    annotate(engine, "_execute", "ServingEngine._execute")
    annotate(engine, "_plan_window", "ServingEngine._plan_window")
    compiles = []
    on = {"window": False}
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_k: compiles.append(ev)
        if on["window"] and ev.endswith("backend_compile_duration") else None)

    # the same traffic for a few seconds before the window, so that the
    # window opens on a running engine (threads, queues, allocator)
    warm = traffic_gen.schedule(traffic, seed + 1, traffic["warm_seconds"],
                                len(pool["val_y"]))
    serve_window(engine, pool, warm)
    setup_s = clock.since()
    s0 = dict(engine.stats)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        traces.start(tdir)
    on["window"] = True
    with GcClock() as gclock, jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
        t0, issued, done, results = serve_window(engine, pool, sched, picks)
    on["window"] = False
    if trace:
        jax.profiler.stop_trace()
    for t in threading.enumerate():
        if t.name == "serving-engine-assembly":
            t.join(timeout=60)
    device = device_info(devs)
    s1 = dict(engine.stats)

    n = len(sched["rows"])
    due = t0 + sched["due"]
    ok = ~np.isnan(done)
    lat = (done - due)[ok]
    window_s = float(np.nanmax(done) - t0)
    picks = np.asarray([i for i in picks if results[i] is not None])
    prog = program_scores(cfg, sched, picks, results)
    results = None
    del engine
    t_check = time.perf_counter()
    ref = reference_scores(cfg, traffic, pool, sched, picks, seed)
    readings = checks.serve_readings(prog, ref)
    check_s = time.perf_counter() - t_check

    rows_by_route = {r: int(sched["rows"][sched["route"] == i].sum())
                     for i, r in enumerate(traffic_gen.ROUTES)}
    run = {"window_s": window_s, "requests": n, "setup_s": setup_s,
           "compiles_in_window": len(compiles), "check_s": check_s,
           "lag_s": issued - due,
           "build_s": s1["build_seconds"] - s0["build_seconds"],
           "execute_s": s1["execute_seconds"] - s0["execute_seconds"],
           "batches": s1["batches"] - s0["batches"],
           "windows": -(-n // traffic["window"]),
           "flops": float(sum(rows_by_route[r] * forward_row(cfg, r)
                              for r in rows_by_route)),
           "probe": probe(t0, sched, issued, done, gclock.pauses)}
    if trace:
        run.update(traces.reduce(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    return {"device": device, "readings": readings, "run": run,
            "attempted": n, "failed": int(n - ok.sum()),
            "e2e": {"setup_s": setup_s,
                    "serve_p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "serve_p95_ms": float(np.percentile(lat, 95) * 1e3),
                    "serve_rps": float(ok.sum() / window_s)}}

"""Federated training driver: ragged clients -> sharded round -> resumable.

Wires the three pieces that turn ``federation_sharded``'s round function
into a runnable, crash-safe system:

    partitioned ragged data  ->  FederatedBatcher (padded masked batches,
                                 double-buffered host->device transfer)
                             ->  jitted make_blendfl_round(state, batch)
                             ->  periodic save_checkpoint of the FULL
                                 round state (stacked client models, opt
                                 moments, server head + srv_opt,
                                 last_round, round counter)

Resume is **bit-exact**: the batcher's round-``r`` batch is a pure
function of ``(seed, r)`` and the checkpoint carries every leaf of
``init_round_state``, so a killed-and-resumed run produces byte-identical
round metrics to an uninterrupted one (``--selftest-resume`` asserts
this; the ``make train-federated`` smoke lane runs it).

    PYTHONPATH=src python -m repro.launch.train_federated \
        --rounds 8 --clients 8 --ckpt-dir /tmp/fedckpt --ckpt-every 2
    PYTHONPATH=src python -m repro.launch.train_federated --selftest-resume

Out-of-core federations: the one-shot ``import`` subcommand converts the
in-memory synthetic partition to a ``repro.data.store.ClientStore`` of
per-client shard files, and ``--store-dir`` runs the federation straight
off those shards — ``build()`` memory-maps only the drawn row subsets, so
peak host RSS per round is O(K*N*row_bytes) regardless of dataset size,
and round-state checkpoints carry the store fingerprint so a resume
against a different store fails loudly instead of silently diverging.

    PYTHONPATH=src python -m repro.launch.train_federated import \
        --store-dir /tmp/fedstore --clients 32 --n-train 65536
    PYTHONPATH=src python -m repro.launch.train_federated \
        --store-dir /tmp/fedstore --rounds 8 --ckpt-dir /tmp/fedckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (latest_step, read_manifest, restore_checkpoint,
                              save_checkpoint)
from repro.core import state as rstate
from repro.core.federation_sharded import (
    ShardedFedSpec,
    batch_specs,
    init_round_state,
    make_blendfl_round,
)
from repro.core.aggregate import SERVER_OPTS, STRATEGIES
from repro.core.codec import CODECS, make_codec, round_bytes
from repro.core.partitioner import ClientData, partition
from repro.core.schedule import POLICIES, telemetry_from_state
from repro.data.pipeline import FederatedBatcher
from repro.data.scenario import load_scenario
from repro.data.store import ClientStore, write_store
from repro.data.synthetic import make_task, train_val_test
from repro.launch import shardings as sh
from repro.launch.mesh import make_host_mesh
from repro.launch.runtime import device_line, use_compile_cache


def client_arrays(cd: ClientData) -> dict:
    """``partitioner.ClientData`` -> the FederatedBatcher's dict-of-arrays
    client format (labels for fragmented rows ride with the a side)."""
    return {
        "partial_a": cd.partial_a.x, "partial_ya": cd.partial_a.y,
        "partial_b": cd.partial_b.x, "partial_yb": cd.partial_b.y,
        "frag_a": cd.frag_a.x, "frag_y": cd.frag_a.y,
        "frag_ids_a": cd.frag_a.ids,
        "frag_b": cd.frag_b.x, "frag_ids_b": cd.frag_b.ids,
        "paired_a": cd.paired_a.x, "paired_b": cd.paired_b.x,
        "paired_y": cd.paired_a.y,
    }


def import_store(args) -> ClientStore:
    """One-shot conversion: in-memory synthetic partition -> on-disk
    ``ClientStore``. The manifest records the task dims, seeds, and val
    size, so a later ``--store-dir`` run is fully self-describing (no
    data-generation args needed, no dataset materialized in host RAM)."""
    if not args.store_dir:
        raise SystemExit("import requires --store-dir")
    task = make_task(args.task)
    tr, va, _ = train_val_test(task, args.n_train, args.n_val, 64,
                               seed=args.data_seed)
    clients = partition(tr, args.clients, seed=args.data_seed,
                        dirichlet_alpha=args.dirichlet_alpha)
    meta = {"task": args.task, "kind": task.kind, "out_dim": task.out_dim,
            "seq_a": task.seq_a, "feat_a": task.feat_a,
            "seq_b": task.seq_b, "feat_b": task.feat_b,
            "n_train": args.n_train, "n_val": args.n_val,
            "data_seed": args.data_seed,
            "dirichlet_alpha": args.dirichlet_alpha}
    store = write_store(args.store_dir, [client_arrays(cd) for cd in clients],
                        {"val_a": va.x_a, "val_b": va.x_b, "val_y": va.y},
                        meta=meta, overwrite=args.overwrite)
    rows = sum(store.rows(c, k) for c in range(store.n_clients)
               for k in store.client_keys(c))
    print(f"imported {store.n_clients} clients ({rows} shard rows, task "
          f"{args.task!r}) -> {args.store_dir}  "
          f"[fingerprint {store.fingerprint()[:12]}]")
    return store


def build_federation(args, devices=None) -> tuple:
    """(spec, batcher, round_fn, mesh) for a ragged federation — in-memory
    synthetic by default, out-of-core when ``--store-dir`` names an
    imported ``ClientStore`` (client arrays then stay on disk; only the
    drawn row subsets are ever materialized). The client axis shards over
    ``devices`` (default: every local device)."""
    # static per-round capacities sized to the ragged partition
    n_partial = max(args.rows_cap, 1)
    scenario = None
    if getattr(args, "scenario", None):
        scenario = load_scenario(args.scenario)
        if getattr(args, "store_dir", None):
            raise SystemExit(
                "--scenario does not compose with --store-dir: a store's "
                "client count is fixed at import, a scenario's roster "
                "grows — partition in-memory data instead")
    store = None
    if getattr(args, "store_dir", None):
        store = ClientStore(args.store_dir)
        m = store.meta  # dims recorded at import time, not CLI args
        spec = ShardedFedSpec(
            n_clients=store.n_clients, d_hidden=args.d_hidden,
            n_layers=args.n_layers, seq_a=m["seq_a"], feat_a=m["feat_a"],
            seq_b=m["seq_b"], feat_b=m["feat_b"], out_dim=m["out_dim"],
            kind=m["kind"], n_partial=n_partial, n_frag=n_partial,
            n_paired=n_partial, n_val=m["n_val"], lr=args.lr,
            optimizer=args.optimizer, n_sampled=args.n_sampled,
            policy=getattr(args, "policy", "uniform"),
            codec=getattr(args, "codec", "none"),
            topk_frac=getattr(args, "topk_frac", 0.25),
            strategy=getattr(args, "strategy", "blendavg"),
            fedprox_mu=getattr(args, "fedprox_mu", 0.0),
            server_opt=getattr(args, "server_opt", "none"),
            server_lr=getattr(args, "server_lr", 1.0),
            n_malicious=getattr(args, "n_malicious", 1))
    else:
        task = make_task(args.task)
        tr, va, _ = train_val_test(task, args.n_train, args.n_val, 64,
                                   seed=args.data_seed)
        # under a scenario the FULL roster (initial cohort + every future
        # joiner) is partitioned up-front — a joiner's data exists from
        # round 0 but its slot stays inactive until its join event — and
        # spec.n_clients is the state CAPACITY for the cohort size at the
        # (possibly resumed) start round, bucketed so growth recompiles
        # at most once per bucket
        n_part = args.clients
        n_cap = args.clients
        if scenario is not None:
            scenario.validate(args.clients)
            n_part = args.clients + scenario.total_joins()
            r0 = ((latest_step(args.ckpt_dir) or 0)
                  if getattr(args, "ckpt_dir", None) else 0)
            n_cap = rstate.capacity_for(
                scenario.n_clients_at(r0 - 1, args.clients))
        clients = partition(tr, n_part, seed=args.data_seed,
                            dirichlet_alpha=args.dirichlet_alpha)
        spec = ShardedFedSpec(
            n_clients=n_cap, d_hidden=args.d_hidden, n_layers=args.n_layers,
            seq_a=task.seq_a, feat_a=task.feat_a, seq_b=task.seq_b,
            feat_b=task.feat_b, out_dim=task.out_dim, kind=task.kind,
            n_partial=n_partial, n_frag=n_partial, n_paired=n_partial,
            n_val=args.n_val, lr=args.lr, optimizer=args.optimizer,
            n_sampled=args.n_sampled, policy=getattr(args, "policy", "uniform"),
            codec=getattr(args, "codec", "none"),
            topk_frac=getattr(args, "topk_frac", 0.25),
            strategy=getattr(args, "strategy", "blendavg"),
            fedprox_mu=getattr(args, "fedprox_mu", 0.0),
            server_opt=getattr(args, "server_opt", "none"),
            server_lr=getattr(args, "server_lr", 1.0),
            n_malicious=getattr(args, "n_malicious", 1),
            # gradient-space attackers ride the scenario: the flag is
            # static round structure (the attack hook + attack_coef
            # batch key trace in), WHO attacks each round is data
            attacks=(scenario.has_uplink_attacks()
                     if scenario is not None else False))
    mesh = make_host_mesh(devices=devices)
    n_data = mesh.shape["data"]
    if spec.k_round % n_data:
        raise ValueError(
            f"{spec.k_round} clients train per round, which the {n_data}-way "
            "data axis of the device mesh does not divide: choose --clients "
            f"(or --n-sampled) as a multiple of {n_data}")
    shard = sh.batch_shardings(mesh, batch_specs(spec, ragged=True))
    if store is not None:
        batcher = FederatedBatcher.from_store(
            store, spec, seed=args.seed, shardings=shard,
            prefetch=args.prefetch)
    else:
        batcher = FederatedBatcher(
            [client_arrays(cd) for cd in clients], spec,
            {"val_a": va.x_a, "val_b": va.x_b, "val_y": va.y},
            seed=args.seed, shardings=shard, prefetch=args.prefetch,
            scenario=scenario, n_initial=args.clients)
    return spec, batcher, jit_round(spec, mesh), mesh


def jit_round(spec: ShardedFedSpec, mesh):
    """The jitted round, its outputs pinned replicated on ``mesh``: on a
    multi-device mesh GSPMD would otherwise choose other shardings for
    the returned state, and the next round would compile a second
    program."""
    return jax.jit(make_blendfl_round(spec),
                   out_shardings=NamedSharding(mesh, P()))


def place_state(state: dict, mesh) -> dict:
    """Put a fresh/restored round state on the mesh with the same
    (replicated) shardings the jitted round emits — keeps the round's
    compile cache at exactly one entry across init, chaining, and
    resume (a SingleDeviceSharding state would retrace once)."""
    return jax.device_put(state, sh.replicated(mesh, state))


def run(args, spec, batcher, round_fn, start: int, state: dict,
        log=print) -> list[dict]:
    """Drive rounds [start, args.rounds), checkpointing the full round
    state every ``ckpt_every`` rounds. Returns per-round metric dicts."""
    history = []
    # store-backed runs stamp the data identity into every checkpoint so
    # init_or_restore can refuse to resume against a different store
    fp = _fingerprint(batcher)

    def sched_telemetry() -> dict:
        # state-reading participation policies (staleness / omega_ema)
        # pull the sched block before each build; ``state`` rebinds every
        # round below, so this always reads the latest round's telemetry
        return telemetry_from_state(state)

    t0 = time.perf_counter()
    for r, batch in batcher.rounds(start, args.rounds,
                                   telemetry_fn=sched_telemetry):
        state, row = _step(round_fn, state, batch, r)
        history.append(row)
        if args.log_every and (r + 1) % args.log_every == 0:
            log(f"round {r + 1:4d} loss_uni {row['loss_uni']:.4f} "
                f"loss_vfl {row['loss_vfl']:.4f} "
                f"loss_paired {row['loss_paired']:.4f} "
                f"({time.perf_counter() - t0:.2f}s this round)")
        if args.ckpt_dir and args.ckpt_every and (r + 1) % args.ckpt_every == 0:
            meta = {"round": r + 1, "loss_uni": row["loss_uni"]}
            if fp is not None:
                meta["store_fingerprint"] = fp
            out = save_checkpoint(args.ckpt_dir, r + 1, state, meta)
            log(f"checkpointed round {r + 1} -> {out}")
        t0 = time.perf_counter()
    return history


def _step(round_fn, state: dict, batch: dict, r: int) -> tuple[dict, dict]:
    """One round: dispatch ``round_fn`` in a ``train_federated.dispatch``
    span, then read its metrics back to the host in a
    ``train_federated.sync`` span. Returns (state', the round's scalar
    metrics as floats, with ``round``)."""
    with TraceAnnotation("train_federated.dispatch", round=r):
        state, metrics = round_fn(state, batch)
    with TraceAnnotation("train_federated.sync", round=r):
        host = {k: np.asarray(v) for k, v in metrics.items()}
    row = {k: float(v) for k, v in host.items() if v.ndim == 0}
    row["round"] = r
    return state, row


def _fingerprint(batcher) -> str | None:
    return batcher.store.fingerprint() if batcher.store is not None else None


def run_scenario(args, spec, batcher, round_fn, mesh, start: int, state: dict,
                 log=print):
    """Drive rounds [start, args.rounds) under the batcher's churn
    scenario: before each round, apply its events — grow the state to the
    round's capacity bucket (one re-jit per NEW bucket; the per-bucket
    round functions live in the returned dict and each compiles exactly
    once), retire departing clients' state rows — then build the round
    batch against the scenario's active mask. Returns
    ``(history, round_fns, spec, state)``.

    Membership is a pure function of the round index, so a resumed run
    replays the identical capacity/event sequence from ``start`` and the
    bit-exact resume contract survives churn unchanged.
    """
    scenario = batcher.scenario
    round_fns = {spec.n_clients: round_fn}
    history = []
    fp = _fingerprint(batcher)
    for r in range(start, args.rounds):
        t0 = time.perf_counter()
        ev = scenario.events_at(r)
        n_now = scenario.n_clients_at(r, batcher.n_initial)
        cap = rstate.capacity_for(n_now)
        if cap > spec.n_clients:
            log(f"round {r}: cohort grows to {n_now} clients -> capacity "
                f"{cap} (new bucket, one re-jit)")
            state = place_state(rstate.grow(state, cap), mesh)
            spec = dataclasses.replace(spec, n_clients=cap)
            batcher.set_spec(spec)
            if cap not in round_fns:
                round_fns[cap] = jit_round(spec, mesh)
        if ev is not None and ev.leave:
            log(f"round {r}: clients {list(ev.leave)} depart "
                "(state rows retired, never sampled again)")
            state = place_state(rstate.retire_clients(state, ev.leave), mesh)
        if ev is not None and ev.corrupt:
            log(f"round {r}: clients {list(ev.corrupt)} turn adversarial "
                "(labels flipped from this round on)")
        if ev is not None and (ev.sign_flip or ev.scale or ev.backdoor):
            parts = [f"{kind} {list(ids)}" for kind, ids in
                     (("sign_flip", ev.sign_flip), ("scale", ev.scale),
                      ("backdoor", ev.backdoor)) if ids]
            log(f"round {r}: gradient-space attackers from this round on: "
                + ", ".join(parts))
        sched = (telemetry_from_state(state)
                 if batcher.policy is not None and batcher.policy.needs_state
                 else None)
        batch = batcher.put(batcher.build(r, sched), r)
        state, row = _step(round_fns[spec.n_clients], state, batch, r)
        history.append(row)
        if args.log_every and (r + 1) % args.log_every == 0:
            log(f"round {r + 1:4d} loss_uni {row['loss_uni']:.4f} "
                f"loss_vfl {row['loss_vfl']:.4f} "
                f"loss_paired {row['loss_paired']:.4f} "
                f"[{n_now} clients / cap {spec.n_clients}] "
                f"({time.perf_counter() - t0:.2f}s this round)")
        if args.ckpt_dir and args.ckpt_every and (r + 1) % args.ckpt_every == 0:
            meta = {"round": r + 1, "loss_uni": row["loss_uni"]}
            if fp is not None:
                meta["store_fingerprint"] = fp
            out = save_checkpoint(args.ckpt_dir, r + 1, state, meta)
            log(f"checkpointed round {r + 1} -> {out}")
    return history, round_fns, spec, state


def init_or_restore(args, spec, mesh, store_fingerprint: str | None = None
                    ) -> tuple[int, dict]:
    """Fresh ``init_round_state`` or the latest full-state checkpoint.

    ``store_fingerprint`` is the current run's ``ClientStore`` identity
    (None for in-memory data). A checkpoint stamped with a *different*
    fingerprint belongs to another federation's data — resuming would
    silently break the bit-exact batch-stream contract, so it raises.
    """
    state = init_round_state(jax.random.PRNGKey(args.seed), spec)
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start = latest_step(args.ckpt_dir)
        manifest = read_manifest(args.ckpt_dir, start)
        want = manifest.get("metadata", {}).get("store_fingerprint")
        if want is not None and store_fingerprint is None:
            raise ValueError(
                f"checkpoint at {args.ckpt_dir} round {start} was written "
                "by a store-backed run (store_fingerprint "
                f"{want[:12]}…) — resume it with the same --store-dir, "
                "not in-memory data")
        if want is not None and want != store_fingerprint:
            raise ValueError(
                f"checkpoint at {args.ckpt_dir} round {start} was written "
                f"against a different client store (fingerprint {want[:12]}… "
                f"vs current {store_fingerprint[:12]}…) — refusing to "
                "resume: the (seed, round) batch stream would diverge")
        if want is None and store_fingerprint is not None:
            print("note: resuming a checkpoint with no store fingerprint "
                  "from a store-backed run (ok if the store was imported "
                  "from the same dataset)")
        # capacity migration: a checkpoint stacked for fewer client slots
        # restores bit-exactly into its own capacity, then grows — never
        # silently reinitializes; shrinking in place is refused outright
        ckpt_cap = rstate.manifest_capacity(manifest)
        if ckpt_cap > spec.n_clients:
            raise ValueError(
                f"checkpoint at {args.ckpt_dir} round {start} holds "
                f"{ckpt_cap} client slots but this federation was built "
                f"for {spec.n_clients} — shrinking a cohort in place is "
                f"not supported (retire clients via a scenario instead); "
                f"rerun with --clients >= {ckpt_cap}")
        if ckpt_cap < spec.n_clients:
            print(f"migrating checkpoint: {ckpt_cap} client slots -> "
                  f"capacity {spec.n_clients} (existing rows restore "
                  "bit-exactly; new rows take each block's declared fill)")
            template = init_round_state(
                jax.random.PRNGKey(args.seed),
                dataclasses.replace(spec, n_clients=ckpt_cap))
            state = rstate.grow(
                restore_checkpoint(args.ckpt_dir, template, step=start),
                spec.n_clients)
        else:
            state = restore_checkpoint(args.ckpt_dir, state, step=start)
        print(f"restored full round state at round {start} from {args.ckpt_dir}")
    return start, place_state(state, mesh)


def selftest_resume(args) -> None:
    """Smoke assertion: an interrupted-and-resumed federation reproduces
    the uninterrupted run's round metrics bit-for-bit."""
    import tempfile

    assert args.rounds >= 2, "resume selftest needs >= 2 rounds"
    mid = args.rounds // 2
    spec, batcher, round_fn, mesh = build_federation(args)

    # uninterrupted reference — never writes to a user --ckpt-dir
    ref_args = argparse.Namespace(**{**vars(args), "ckpt_dir": None})
    ref = run(ref_args, spec, batcher, round_fn, 0, place_state(
        init_round_state(jax.random.PRNGKey(args.seed), spec), mesh))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        a = argparse.Namespace(**{**vars(args), "ckpt_dir": ckpt_dir,
                                  "ckpt_every": mid, "rounds": mid})
        part1 = run(a, spec, batcher, round_fn, 0, place_state(
            init_round_state(jax.random.PRNGKey(args.seed), spec), mesh))
        # "crash": rebuild everything from scratch, restore from disk
        spec2, batcher2, round_fn2, mesh2 = build_federation(args)
        a2 = argparse.Namespace(**{**vars(args), "ckpt_dir": ckpt_dir})
        start, state = init_or_restore(a2, spec2, mesh2, _fingerprint(batcher2))
        assert start == mid, f"expected restore at round {mid}, got {start}"
        part2 = run(a2, spec2, batcher2, round_fn2, start, state)
    # round_fn saw fresh-init + chained states; round_fn2 saw a RESTORED
    # state + chained — each wrapper must have compiled exactly once (a
    # place_state regression would retrace on one of them)
    assert int(round_fn._cache_size()) == 1, \
        "fresh-init + chained rounds must share one compiled program"
    assert int(round_fn2._cache_size()) == 1, \
        "restored + chained rounds must share one compiled program"

    resumed = part1 + part2
    assert len(resumed) == len(ref)
    for got, want in zip(resumed, ref):
        for k in want:
            if not (got[k] == want[k] or (np.isnan(got[k]) and np.isnan(want[k]))):
                raise AssertionError(
                    f"resume parity broken at round {want['round']}: "
                    f"{k} {got[k]!r} != {want[k]!r}")
    print(f"resume parity OK: {len(ref)} rounds bit-identical "
          f"(interrupted at round {mid}, n_sampled={args.n_sampled}, "
          f"policy={getattr(args, 'policy', 'uniform')})")


def selftest_resume_scenario(args) -> None:
    """Churn resume smoke: a federation killed and resumed mid-scenario —
    across a cohort-growth event — reproduces the uninterrupted run's
    round metrics bit-for-bit, with every capacity bucket's round
    function compiling exactly once in every leg."""
    import tempfile

    assert args.rounds >= 2, "resume selftest needs >= 2 rounds"
    mid = args.rounds // 2

    def fresh(a):
        spec, batcher, round_fn, mesh = build_federation(a)
        start, state = init_or_restore(a, spec, mesh, None)
        return spec, batcher, round_fn, mesh, start, state

    def check_caches(fns, leg):
        for cap, fn in fns.items():
            n = int(fn._cache_size())
            assert n == 1, (f"{leg}: capacity-{cap} round function "
                            f"compiled {n}x (expected exactly once)")

    spec, batcher, round_fn, mesh, _, state = fresh(
        argparse.Namespace(**{**vars(args), "ckpt_dir": None}))
    scenario = batcher.scenario
    joins = [e.round for e in scenario.events if e.join]
    assert joins and min(joins) < args.rounds, \
        "the scenario resume selftest needs a join event inside the run"
    caps_seen = {rstate.capacity_for(scenario.n_clients_at(r, args.clients))
                 for r in range(args.rounds)}

    ref_args = argparse.Namespace(**{**vars(args), "ckpt_dir": None})
    ref, ref_fns, _, _ = run_scenario(ref_args, spec, batcher, round_fn,
                                      mesh, 0, state)
    check_caches(ref_fns, "reference")
    assert len(ref_fns) == len(caps_seen), \
        f"{len(ref_fns)} compiled buckets for {len(caps_seen)} capacities"

    with tempfile.TemporaryDirectory() as ckpt_dir:
        a1 = argparse.Namespace(**{**vars(args), "ckpt_dir": ckpt_dir,
                                   "ckpt_every": mid, "rounds": mid})
        spec1, b1, fn1, mesh1, _, st1 = fresh(a1)
        part1, fns1, _, _ = run_scenario(a1, spec1, b1, fn1, mesh1, 0, st1)
        check_caches(fns1, "pre-kill")
        # "crash": rebuild from scratch; build_federation sizes the spec
        # to the checkpointed round's capacity, init_or_restore restores
        a2 = argparse.Namespace(**{**vars(args), "ckpt_dir": ckpt_dir})
        spec2, b2, fn2, mesh2, start, st2 = fresh(a2)
        assert start == mid, f"expected restore at round {mid}, got {start}"
        part2, fns2, _, _ = run_scenario(a2, spec2, b2, fn2, mesh2, start, st2)
        check_caches(fns2, "resumed")

    resumed = part1 + part2
    assert len(resumed) == len(ref)
    for got, want in zip(resumed, ref):
        for k in want:
            if not (got[k] == want[k] or (np.isnan(got[k]) and np.isnan(want[k]))):
                raise AssertionError(
                    f"scenario resume parity broken at round {want['round']}: "
                    f"{k} {got[k]!r} != {want[k]!r}")
    print(f"scenario resume parity OK: {len(ref)} rounds bit-identical "
          f"across churn (interrupted at round {mid}, capacities "
          f"{sorted(caps_seen)}, codec={getattr(args, 'codec', 'none')}, "
          f"strategy={getattr(args, 'strategy', 'blendavg')})")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("command", nargs="?", choices=["import"], default=None,
                    help="'import': convert the synthetic partition to an "
                         "on-disk ClientStore at --store-dir and exit")
    ap.add_argument("--store-dir", default=None,
                    help="run out-of-core from this imported ClientStore "
                         "(training) / write the store here (import)")
    ap.add_argument("--overwrite", action="store_true",
                    help="import: replace an existing store directory")
    ap.add_argument("--task", default="smnist")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--scenario", default=None,
                    help="churn scenario YAML (repro.data.scenario): "
                         "join/leave/corrupt plus gradient-space attack "
                         "events (sign_flip/scale/backdoor) per round; "
                         "requires --n-sampled > 0, grows state capacity "
                         "in buckets (see examples/scenarios/)")
    ap.add_argument("--n-sampled", type=int, default=0)
    ap.add_argument("--policy", default="uniform", choices=POLICIES,
                    help="participation policy for K-of-C sampled rounds "
                         "(repro.core.schedule); uniform = bit-exact "
                         "pre-scheduler sampling")
    ap.add_argument("--codec", default="none", choices=CODECS,
                    help="wire codec for the simulated round traffic "
                         "(repro.core.codec): candidate uplink + broadcast "
                         "downlink deltas with error-feedback residuals")
    ap.add_argument("--strategy", default="blendavg", choices=STRATEGIES,
                    help="aggregation strategy (repro.core.aggregate): "
                         "blendavg scored blend | fedavg volume weights | "
                         "scaffold control variates | fedprox proximal term "
                         "| median / trimmed_mean / krum Byzantine-robust "
                         "reducers (see --n-malicious)")
    ap.add_argument("--n-malicious", type=int, default=1,
                    help="assumed malicious-client budget f for the robust "
                         "strategies (trim count per side / multi-Krum's f)")
    ap.add_argument("--fedprox-mu", type=float, default=0.0,
                    help="FedProx proximal coefficient (requires "
                         "--strategy fedprox; mu 0 = plain fedavg)")
    ap.add_argument("--server-opt", default="none", choices=SERVER_OPTS,
                    help="server-side optimizer on the blended delta "
                         "(composes with any --strategy)")
    ap.add_argument("--server-lr", type=float, default=1.0,
                    help="server-side optimizer learning rate")
    ap.add_argument("--topk-frac", type=float, default=0.25,
                    help="fraction of entries per leaf kept by the "
                         "sparsifying codecs (topk / int8_topk)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--n-train", type=int, default=2048)
    ap.add_argument("--n-val", type=int, default=256)
    ap.add_argument("--rows-cap", type=int, default=64,
                    help="static per-client per-phase row capacity")
    ap.add_argument("--d-hidden", type=int, default=32)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--dirichlet-alpha", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--prefetch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--selftest-resume", action="store_true",
                    help="run the killed-and-resumed parity assertion and exit")
    return ap


def main() -> None:
    args = build_parser().parse_args()

    use_compile_cache()
    print(device_line())
    if args.command == "import":
        import_store(args)
        return
    if args.selftest_resume:
        if args.scenario:
            selftest_resume_scenario(args)
        else:
            selftest_resume(args)
        return
    spec, batcher, round_fn, mesh = build_federation(args)
    start, state = init_or_restore(args, spec, mesh, _fingerprint(batcher))
    if spec.codec != "none":
        rb = round_bytes(state["global_models"],
                         make_codec(spec.codec, spec.topk_frac),
                         n_up=spec.k_round, n_down=spec.k_round)
        print(f"codec {spec.codec} (topk_frac={spec.topk_frac}): "
              f"{rb['bytes_per_round']:,} bytes/round, "
              f"{rb['compression_ratio']:.1f}x vs dense fp32")
    if batcher.scenario is not None:
        run_scenario(args, spec, batcher, round_fn, mesh, start, state)
    else:
        run(args, spec, batcher, round_fn, start, state)
    print(f"done ({args.rounds - start} rounds; host batch-build "
          f"{batcher.build_seconds:.2f}s over {batcher.rounds_built} builds, "
          f"of which reads {batcher.read_seconds:.2f}s for "
          f"{batcher.read_bytes / 1e9:.3f} GB, {batcher.map_opens} shard "
          f"maps opened, {batcher.reads_unmapped} reads unmapped; slab "
          f"sets reused {batcher.slab_reuses} of "
          f"{batcher.slab_reuses + batcher.slab_allocs} builds; device put "
          f"{batcher.put_seconds:.2f}s).")


if __name__ == "__main__":
    main()

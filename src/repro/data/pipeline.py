"""Batching pipelines.

``Batcher`` serves the in-host federated experiments (numpy in,
dict-of-arrays out). ``token_batches`` serves the LM examples (synthetic
token streams). ``FederatedBatcher`` is the federated data subsystem
for the sharded SPMD round: it turns C ragged per-client datasets —
heterogeneous row counts, zero-row modalities included — into the static
``(K, N, ...)`` phase batches ``federation_sharded.make_blendfl_round``
consumes, with real 0/1 masks instead of the uniform all-ones layout.

Design points:

- **Stateless per-round RNG.** Every batch is a pure function of
  ``(seed, round)`` (``np.random.default_rng([seed, round])`` draws the
  row subsets, the VFL alignment, and the K-of-C sampled client ids), so
  a federation resumed from a round-``r`` checkpoint rebuilds the exact
  byte-identical batch stream — the property the round-state
  checkpointing in ``repro.launch.train_federated`` relies on for
  bit-exact resume. Adaptive participation policies
  (``repro.core.schedule``, selected by ``spec.policy``) extend the pure
  inputs to ``(seed, round, sched telemetry)`` — and the telemetry is
  checkpointed round state, so the resume contract survives unchanged.
- **Static shapes, data-dependent masks.** Row counts pad up to the
  spec's ``n_partial``/``n_frag``/``n_paired``; masks mark live rows.
  A client with a zero-row modality gets an all-zero mask and is
  excluded from that phase's parameter/momentum update by the engine's
  ``_where_clients`` semantics. The VFL alignment is rebuilt per round
  from global sample ids: aligned rows keep weight 1, padded or
  partner-less rows weight 0, so the alignment's flattened ``(K*Nf,)``
  shape never changes and the round compiles once.
- **Double-buffered host->device transfer.** ``rounds()`` stages the
  next round's batch on a worker thread (build + ``jax.device_put`` with
  the dry-run shardings from ``repro.launch.shardings``) while the
  device executes the current round, hiding host batch-build time
  behind device compute.
- **Each drawn row copied once.** ``build`` gathers a client's drawn
  rows straight into their block of the ``(K, N, ...)`` slab
  (``take(..., out=, mode="clip")``; a store's shard maps stay open), and
  ``rounds()`` recycles its ``prefetch + 2`` slab sets from round to
  round, so steady-state rounds touch no fresh pages. The private host
  memory a stream holds is O((prefetch + 2) * K * N * row_bytes);
  ``build()`` called directly always returns fresh arrays.
- **Measured where the work happens.** ``build``, ``put`` and the
  prefetch wait open ``FederatedBatcher.build`` / ``.put`` / ``.wait``
  host spans carrying the round number, seen by any running profiler
  session; cumulative counters (``build_seconds``, ``read_seconds`` /
  ``read_bytes`` of the client-array reads inside it, ``put_seconds``,
  ``stall_seconds``, ``rounds_built``, ``slab_allocs`` /
  ``slab_reuses``, and the store's ``map_opens`` / ``reads_unmapped``)
  are plain attributes.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np


class Batcher:
    """Deterministic shuffling batcher over dict-of-arrays datasets."""

    def __init__(self, arrays: dict, batch_size: int, seed: int = 0, drop_remainder: bool = False):
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}
        lens = {len(v) for v in self.arrays.values()}
        assert len(lens) == 1, f"ragged arrays: { {k: len(v) for k, v in self.arrays.items()} }"
        self.n = lens.pop()
        self.batch_size = batch_size
        self.rng = np.random.default_rng(seed)
        self.drop_remainder = drop_remainder

    def __len__(self):
        if self.drop_remainder:
            return self.n // self.batch_size
        return (self.n + self.batch_size - 1) // self.batch_size

    def epoch(self, shuffle: bool = True):
        idx = np.arange(self.n)
        if shuffle:
            self.rng.shuffle(idx)
        stop = self.n - (self.n % self.batch_size) if self.drop_remainder else self.n
        for i in range(0, stop, self.batch_size):
            sel = idx[i : i + self.batch_size]
            if self.drop_remainder and len(sel) < self.batch_size:
                break
            yield {k: v[sel] for k, v in self.arrays.items()}


def token_batches(vocab_size: int, batch: int, seq: int, n_batches: int, seed: int = 0):
    """Synthetic LM token stream with Zipf-ish marginals + copy structure so a
    model can actually reduce loss (used by the e2e training example)."""
    rng = np.random.default_rng(seed)
    for _ in range(n_batches):
        base = rng.zipf(1.3, size=(batch, seq + 1)).astype(np.int64) % vocab_size
        # inject predictable bigram structure: even positions repeat previous token
        base[:, 2::2] = base[:, 1:-1:2]
        yield {"tokens": base[:, :-1].astype(np.int32), "labels": base[:, 1:].astype(np.int32)}


# ------------------------------------------------- federated batch loader --

_F32 = np.float32

# per-client dataset keys the loader understands; all optional (missing or
# zero-row = that client holds no such data)
CLIENT_KEYS = ("partial_a", "partial_ya", "partial_b", "partial_yb",
               "frag_a", "frag_y", "frag_ids_a", "frag_b", "frag_ids_b",
               "paired_a", "paired_b", "paired_y")

_SENTINEL = object()  # end-of-stream marker for the prefetch queue

# the feature slabs of a round batch: what rounds() recycles
_SLAB_KEYS = ("partial_a", "partial_b", "paired_a", "paired_b", "frag_a",
              "frag_b")


def _span(name: str, round_no: int | None):
    """A host span (``jax.profiler.TraceAnnotation``) carrying the round
    number as metadata. Inert, about a microsecond, when no profiler
    session is running."""
    from jax.profiler import TraceAnnotation

    if round_no is None:
        return TraceAnnotation(name)
    return TraceAnnotation(name, round=int(round_no))


def _rows(ds: dict, key: str) -> int:
    v = ds.get(key)
    return 0 if v is None else len(v)


def _aliases(arr, host: np.ndarray) -> bool:
    """Whether a device array's buffers lie in ``host``'s memory: the CPU
    backend may put a numpy array zero-copy, whatever ``may_alias`` says."""
    lo = host.ctypes.data
    hi = lo + host.nbytes
    return any(sh.device.platform == "cpu"
               and lo <= sh.data.unsafe_buffer_pointer() < hi
               for sh in arr.addressable_shards)


def _recycle(pool, host_batch: dict, dev_batch: dict) -> None:
    """Hand a finished round's slab set back to ``pool`` once the device
    arrays put from it are ready, unless one of them aliases its slab (the
    set then belongs to the caller's device batch)."""
    slabs = {k: host_batch[k] for k in _SLAB_KEYS}
    for k, slab in slabs.items():
        arr = dev_batch[k].block_until_ready()
        if _aliases(arr, slab):
            return
    pool.put(slabs)


def _flip(y: np.ndarray, kind: str) -> np.ndarray:
    """Label corruption for a scenario's adversarial clients — the
    deterministic flip from ``repro.data.scenario.flip_labels``."""
    from repro.data.scenario import flip_labels

    return flip_labels(y, kind)


class FederatedBatcher:
    """Federated batch loader: C ragged per-client datasets -> one static
    ``(K, N, ...)`` masked round batch per call, double-buffered to device.

    Parameters
    ----------
    clients : list of per-client dict-of-arrays datasets (see
        ``CLIENT_KEYS``; ``repro.launch.train_federated.client_arrays``
        converts a ``partitioner.ClientData``). Row counts may differ per
        client and any modality may be absent/zero-row.
    spec : ``federation_sharded.ShardedFedSpec`` (duck-typed: only the
        static shape fields and ``n_sampled``/``k_round`` are read). The
        spec's seq/feat/out dims must match the data.
    val : dict with ``val_a``/``val_b``/``val_y`` — the replicated server
        validation set, transferred once and reused in every batch.
    seed : base seed; round ``r``'s batch is a pure function of
        ``(seed, r)`` (crash-safe resume rebuilds the identical stream).
    shardings : optional pytree of shardings matching ``batch_specs()``
        (e.g. from ``repro.launch.shardings.batch_shardings``); passed to
        ``jax.device_put``. None = default placement.
    prefetch : staging depth of ``rounds()``; 0 disables the worker
        thread (build strictly alternates with compute).
    scenario : optional ``repro.data.scenario.Scenario``. The client list
        then covers the FULL roster (initial cohort + every future
        joiner, in join order); ``spec.n_clients`` is the current state
        *capacity* and ``set_spec`` re-binds the loader when the driver
        grows it. Requires sampled rounds (``spec.n_sampled > 0``): batch
        shapes are fixed at K, so membership churn never touches them.
    n_initial : size of the round-0 cohort under a scenario (defaults to
        the full roster — i.e. no pending joiners).
    """

    def __init__(self, clients: list, spec, val: dict, *, seed: int = 0,
                 shardings=None, prefetch: int = 1, scenario=None,
                 n_initial: int | None = None):
        # dict(c) also accepts the lazy mapping views of a ClientStore
        # (values stay ShardRows — no shard data is read at init)
        self._roster = [dict(c) for c in clients]
        self.store = None  # set by from_store; used for checkpoint identity
        self.scenario = scenario
        self.n_initial = (len(self._roster) if n_initial is None
                          else int(n_initial))
        if scenario is None:
            if len(self._roster) != spec.n_clients:
                raise ValueError(f"{len(self._roster)} client datasets for "
                                 f"spec.n_clients={spec.n_clients}")
        else:
            if not getattr(spec, "n_sampled", 0):
                raise ValueError(
                    "a churn scenario requires sampled rounds (n_sampled "
                    "> 0): the phase batches are stacked at K, so only the "
                    "state capacity — never the batch shapes — grows")
            scenario.validate(self.n_initial)
            need = self.n_initial + scenario.total_joins()
            if len(self._roster) < need:
                raise ValueError(
                    f"scenario needs {need} client datasets (initial "
                    f"{self.n_initial} + {scenario.total_joins()} joiners) "
                    f"but the roster holds {len(self._roster)}")
        paired_keys = [("frag_a", "frag_ids_a"), ("frag_b", "frag_ids_b"),
                       ("frag_a", "frag_y"), ("partial_a", "partial_ya"),
                       ("partial_b", "partial_yb"), ("paired_a", "paired_b"),
                       ("paired_a", "paired_y")]
        for i, c in enumerate(self._roster):
            for k in c:
                if k not in CLIENT_KEYS:
                    raise KeyError(f"unknown client dataset key {k!r}")
            for ka, kb in paired_keys:
                if _rows(c, ka) != _rows(c, kb):
                    raise ValueError(
                        f"client {i}: {ka} has {_rows(c, ka)} rows but {kb} "
                        f"has {_rows(c, kb)} — per-client arrays of one "
                        "group must align row-for-row")
        self.seed = int(seed)
        self.shardings = shardings
        self.prefetch = int(prefetch)
        self._bind_spec(spec)
        self.build_seconds = 0.0  # cumulative host batch-build time
        self.stall_seconds = 0.0  # prefetch mode: consumer time blocked
        # waiting for a staged batch (the build time prefetch FAILED to hide)
        self.rounds_built = 0
        # inside build: host time and bytes of the client-array reads (a
        # store's memory-map gathers); the rest of build is slab assembly
        self.read_seconds = 0.0
        self.read_bytes = 0
        self.put_seconds = 0.0  # host time in put (the device transfer)
        # slab sets build allocated fresh / filled again from rounds()' pool
        self.slab_allocs = 0
        self.slab_reuses = 0
        self._lent = threading.local()  # .slabs: the set rounds() lends build
        # the replicated val set never changes: transfer once, with the
        # configured shardings so the jitted round never re-shards it
        import jax

        self._val = {
            k: jax.device_put(np.ascontiguousarray(val[k], _F32),
                              None if shardings is None else shardings.get(k))
            for k in ("val_a", "val_b", "val_y")}

    def _bind_spec(self, spec):
        """Bind the loader to a spec (capacity): slice/pad the roster view
        to ``spec.n_clients`` slots ({}-padded slots hold no data and are
        masked inactive by the scenario), rebuild the per-client row
        totals, and re-instantiate the participation policy at the new C.
        The policy is stateless host code, so re-binding changes nothing
        about rng consumption for a given (telemetry, k)."""
        from repro.core.schedule import make_policy

        self.spec = spec
        view = self._roster[: spec.n_clients]
        self.clients = view + [{}] * (spec.n_clients - len(view))
        policy_name = getattr(spec, "policy", "uniform")
        if getattr(spec, "n_sampled", 0):
            self.policy = make_policy(policy_name, spec.n_clients,
                                      spec.k_round)
        elif policy_name != "uniform":
            raise ValueError(f"participation policy {policy_name!r} requires "
                             "spec.n_sampled > 0 (full participation has "
                             "nothing to schedule)")
        else:
            self.policy = None
        self._client_rows = np.asarray(
            [sum(_rows(c, k) for k in ("partial_a", "partial_b", "frag_a",
                                       "frag_b", "paired_a"))
             for c in self.clients], np.float64)

    @property
    def map_opens(self) -> int:
        """Shard maps the store opened and holds (0 without a store)."""
        return 0 if self.store is None else self.store.maps.map_opens

    @property
    def reads_unmapped(self) -> int:
        """Store reads past the descriptor budget (open, gather, close)."""
        return 0 if self.store is None else self.store.maps.reads_unmapped

    def set_spec(self, spec) -> None:
        """Re-bind after the driver grew the state capacity (a scenario
        join crossed a bucket): same roster, new ``spec.n_clients``."""
        self._bind_spec(spec)

    @classmethod
    def from_store(cls, store, spec, val: dict | None = None, *, seed: int = 0,
                   shardings=None, prefetch: int = 1) -> "FederatedBatcher":
        """Out-of-core loader over a ``repro.data.store.ClientStore``.

        Client arrays stay on disk: ``build()`` gathers only the drawn
        rows through each shard's memory map, which the store holds open
        (shared, reclaimable page cache); the private host memory is the
        slabs, independent of the total dataset size. Row counts,
        dtype/shape validation, and ``_draw`` sizing come from the store
        manifest (no file IO), and the batch stream is bit-identical to an
        in-memory ``FederatedBatcher`` over the same arrays for the same
        ``(seed, round)``. ``val=None`` reads the server validation set
        the store's ``import`` recorded.
        """
        b = cls(store.clients(), spec, store.val() if val is None else val,
                seed=seed, shardings=shardings, prefetch=prefetch)
        b.store = store
        return b

    # ---- static interface ----

    def batch_specs(self) -> dict:
        """ShapeDtypeStructs of every key a round batch carries (the
        ragged superset of ``federation_sharded.batch_specs``, including
        ``perm_b`` and — under sampling — ``sampled``)."""
        from repro.core.federation_sharded import batch_specs

        return batch_specs(self.spec, ragged=True)

    # ---- host-side batch construction (pure in (seed, round)) ----

    def _draw(self, rng, avail: int, cap: int) -> np.ndarray:
        """Row subset for one (client, phase): all rows when they fit,
        else a without-replacement subsample of the static capacity."""
        if avail <= cap:
            return np.arange(avail)
        return rng.permutation(avail)[:cap]

    def build(self, round_no: int, sched: dict | None = None) -> dict:
        """Build round ``round_no``'s host batch (numpy, unsharded), in a
        ``FederatedBatcher.build`` span, counted in ``build_seconds``.

        ``sched`` is the round-state telemetry block (numpy ``omega_ema``
        / ``part_count`` / ``last_round``) a state-reading participation
        policy selects from; policies that don't read state (uniform,
        round_robin, data_volume) ignore it, keeping the batch a pure
        function of ``(seed, round)``. With telemetry, purity extends to
        ``(seed, round, sched)`` — and sched is checkpointed round state,
        so bit-exact resume holds for every policy.
        """
        t0 = time.perf_counter()
        with _span("FederatedBatcher.build", round_no):
            batch = self._assemble(round_no, sched)
        self.build_seconds += time.perf_counter() - t0
        self.rounds_built += 1
        return batch

    def _read(self, rows, sel) -> np.ndarray:
        """``rows[sel]`` of one client array, counted in ``read_seconds``
        and ``read_bytes``."""
        t0 = time.perf_counter()
        out = rows[sel]
        self.read_seconds += time.perf_counter() - t0
        self.read_bytes += out.nbytes
        return out

    def _read_into(self, rows, sel, out: np.ndarray) -> None:
        """``rows[sel]`` gathered straight into ``out`` (a client's block
        of a slab), counted like ``_read``. ``mode="clip"`` keeps numpy
        from buffering the output; ``sel`` is in range by construction."""
        t0 = time.perf_counter()
        rows.take(sel, axis=0, out=out, mode="clip")
        self.read_seconds += time.perf_counter() - t0
        self.read_bytes += (len(sel) * int(np.prod(rows.shape[1:]))
                            * rows.dtype.itemsize)

    def _assemble(self, round_no: int, sched: dict | None) -> dict:
        """The body of ``build``. Fills the slab set ``rounds()`` lent this
        thread, else fresh ones; every element is written (drawn rows, then
        zeros to the end of each client's block), so a recycled set
        carries nothing over from the round it last held."""
        s = self.spec
        lent = getattr(self._lent, "slabs", None)
        if lent is None:
            self.slab_allocs += 1
        else:
            self.slab_reuses += 1

        def slab(key: str, rows_seq_feat: tuple) -> np.ndarray:
            if lent is None:
                return np.empty((s.k_round,) + rows_seq_feat, _F32)
            return lent[key]

        rng = np.random.default_rng([self.seed, int(round_no)])
        K = s.k_round
        if s.n_sampled:
            t = {"round": int(round_no), "rows": self._client_rows}
            if self.scenario is not None:
                # membership is a pure function of the round index, so a
                # resumed run rebuilds the identical mask (and stream)
                t["active"] = self.scenario.active_mask(
                    int(round_no), self.n_initial, s.n_clients)
            if sched is not None:
                t.update(sched)
            elif self.policy.needs_state:
                raise ValueError(
                    f"policy {self.policy.name!r} selects clients from "
                    "round-state telemetry; build() needs the sched block "
                    "(drive it via rounds(..., telemetry_fn=...))")
            # the uniform policy consumes this rng exactly like the
            # pre-scheduler code (one choice draw), so the whole batch
            # stream stays bit-identical under the default policy
            idx = self.policy.select(rng, t)
        else:
            idx = np.arange(s.n_clients)
        sub = [self.clients[i] for i in idx]
        flip = [False] * len(idx)
        bdoor = [False] * len(idx)
        if self.scenario is not None:
            bad = set(self.scenario.corrupt_ids(int(round_no)))
            flip = [int(i) in bad for i in idx]
            bd = set(self.scenario.backdoor_ids(int(round_no)))
            bdoor = [int(i) in bd for i in idx]

        batch = {}
        # phases 1 & 3: padded slabs + 0/1 row masks
        slabs = [
            ("partial_a", "partial_ya", "partial_ma", s.n_partial, s.seq_a, s.feat_a),
            ("partial_b", "partial_yb", "partial_mb", s.n_partial, s.seq_b, s.feat_b),
            ("paired_a", "paired_y", "paired_m", s.n_paired, s.seq_a, s.feat_a),
            ("paired_b", None, None, s.n_paired, s.seq_b, s.feat_b),
        ]
        paired_sel = [None] * K  # paired rows must align across modalities
        for xk, yk, mk, cap, seq, feat in slabs:
            x = slab(xk, (cap, seq, feat))
            y = np.zeros((K, cap, s.out_dim), _F32) if yk else None
            m = np.zeros((K, cap), _F32) if mk else None
            for k, ds in enumerate(sub):
                if xk == "paired_b":
                    sel = paired_sel[k]  # same rows as paired_a
                else:
                    sel = self._draw(rng, _rows(ds, xk), cap)
                    if xk == "paired_a":
                        paired_sel[k] = sel
                n = len(sel)
                x[k, n:] = 0.0  # pad rows (the whole block when none drawn)
                if n == 0:
                    continue
                self._read_into(ds[xk], sel, x[k, :n])
                if y is not None:
                    y_rows = self._read(ds[yk], sel)
                    y[k, :n] = _flip(y_rows, s.kind) if flip[k] else y_rows
                if bdoor[k]:
                    # targeted backdoor (scenario `backdoor:` events): a
                    # deterministic prefix of the drawn rows gets the
                    # fixed trigger patch + the attacker's target label.
                    # The prefix of the (seed, round)-pure draw adds no
                    # RNG, so poisoned streams resume bit-exactly. The
                    # fragmented (VFL) slabs stay clean: their labels
                    # live server-side, out of the client's reach.
                    from repro.data import scenario as scn
                    nb = scn.backdoor_rows(n)
                    x[k, :nb] = scn.apply_trigger(x[k, :nb])
                    if y is not None:
                        y[k, :nb] = scn.backdoor_target(s.kind, s.out_dim)
                if m is not None:
                    m[k, :n] = 1.0
            batch[xk] = x
            if y is not None:
                batch[yk] = y
            if m is not None:
                batch[mk] = m

        # phase 2: fragmented slabs + id-based alignment (the PSI output).
        # Flattened a-side row i pairs with flattened b-side row
        # perm_b[i]; rows that are padding or whose partner modality was
        # not drawn this round carry weight 0 (static shape, live mask).
        nf = s.n_frag
        fa = slab("frag_a", (nf, s.seq_a, s.feat_a))
        fb = slab("frag_b", (nf, s.seq_b, s.feat_b))
        fy = np.zeros((K, nf, s.out_dim), _F32)
        ids_a = np.full(K * nf, -1, np.int64)
        ids_b = np.full(K * nf, -2, np.int64)  # never matches ids_a padding
        for k, ds in enumerate(sub):
            sel_a = self._draw(rng, _rows(ds, "frag_a"), nf)
            sel_b = self._draw(rng, _rows(ds, "frag_b"), nf)
            fa[k, len(sel_a):] = 0.0
            fb[k, len(sel_b):] = 0.0
            if len(sel_a):
                self._read_into(ds["frag_a"], sel_a, fa[k, : len(sel_a)])
                fy_rows = self._read(ds["frag_y"], sel_a)
                fy[k, : len(sel_a)] = (_flip(fy_rows, s.kind) if flip[k]
                                       else fy_rows)
                ids_a[k * nf : k * nf + len(sel_a)] = self._read(
                    ds["frag_ids_a"], sel_a)
            if len(sel_b):
                self._read_into(ds["frag_b"], sel_b, fb[k, : len(sel_b)])
                ids_b[k * nf : k * nf + len(sel_b)] = self._read(
                    ds["frag_ids_b"], sel_b)
        bpos = np.flatnonzero(ids_b >= 0)
        order = np.argsort(ids_b[bpos], kind="stable")
        sorted_b = ids_b[bpos][order]
        if len(sorted_b):
            loc = np.clip(np.searchsorted(sorted_b, ids_a), 0, len(sorted_b) - 1)
            hit = (ids_a >= 0) & (sorted_b[loc] == ids_a)
            perm_b = np.where(hit, bpos[order][loc], 0)
        else:
            hit = np.zeros(K * nf, bool)
            perm_b = np.zeros(K * nf, np.int64)
        part_a = np.zeros(K, bool)
        part_b = np.zeros(K, bool)
        if hit.any():
            part_a[np.unique(np.flatnonzero(hit) // nf)] = True
            part_b[np.unique(perm_b[hit] // nf)] = True
        fy[~hit.reshape(K, nf)] = 0.0  # padded/unmatched rows carry no label
        batch.update({
            "frag_a": fa, "frag_b": fb, "frag_y": fy,
            "perm_b": perm_b.astype(np.int32),
            "frag_w": hit.astype(_F32),
            "frag_part_a": part_a, "frag_part_b": part_b,
        })
        if s.n_sampled:
            batch["sampled"] = idx.astype(np.int32)
        if getattr(s, "attacks", False):
            # per-participant uplink coefficient (1 honest / -1
            # sign-flip / SCALE_FACTOR boosted) — scenario-derived, pure
            # in the round index; all-ones without a scenario (the
            # bench's no-attack arm shares the attacked arms' compiled
            # round)
            batch["attack_coef"] = (
                self.scenario.attack_coef(int(round_no), idx)
                if self.scenario is not None else np.ones(len(idx), _F32))
        return batch

    def put(self, host_batch: dict, round_no: int | None = None) -> dict:
        """Transfer one host batch to device with the configured
        shardings; the cached val set rides along untouched. A
        ``FederatedBatcher.put`` span, counted in ``put_seconds``."""
        import jax

        t0 = time.perf_counter()
        with _span("FederatedBatcher.put", round_no):
            if self.shardings is not None:
                moved = {k: jax.device_put(v, self.shardings[k])
                         for k, v in host_batch.items()}
            else:
                moved = jax.device_put(host_batch)
        self.put_seconds += time.perf_counter() - t0
        return dict(moved, **self._val)

    # ---- double-buffered round stream ----

    def rounds(self, start: int, stop: int, prefetch: int | None = None,
               telemetry_fn=None):
        """Yield ``(round_no, device_batch)`` for rounds [start, stop).

        With ``prefetch > 0`` a daemon worker builds and stages up to
        ``prefetch`` future HOST batches while the caller's round executes
        on device (numpy slab assembly releases the GIL, and the caller
        blocks in C++ when it reads round metrics — so the build
        genuinely overlaps device compute). The device transfer itself
        stays on the consumer thread: ``jax.device_put`` from a second
        thread contends with the XLA CPU compute pool, and the copy is
        cheap next to the build. ``stall_seconds`` accumulates consumer
        time spent waiting for a staged batch — the build time prefetch
        failed to hide — and a ``FederatedBatcher.wait`` span covers
        each such wait.

        ``telemetry_fn() -> dict`` supplies the current round-state sched
        telemetry for a state-reading participation policy (staleness /
        omega_ema). Round r's selection depends on round r-1's outcome —
        a true data dependency — so those policies run the synchronous
        path regardless of ``prefetch``: each batch builds only after the
        caller's previous round updated the state the telemetry reads.
        State-free policies keep the full prefetch overlap.

        Every path recycles the host slabs: when the caller resumes the
        stream after round r, round r's slab set goes back to a pool once
        the device arrays put from it are ready, and a later ``build``
        (still through ``self.build``) fills it again. A set whose device
        arrays alias it (the CPU backend may put numpy zero-copy) stays
        with the caller's batch; an empty pool means a fresh set."""
        if self.scenario is not None:
            raise ValueError(
                "rounds() cannot stream a churn scenario: capacity (and "
                "with it this loader's spec) may change between rounds — "
                "drive build()/put() round-by-round from the scenario loop")
        needs_state = self.policy is not None and self.policy.needs_state
        if needs_state and telemetry_fn is None:
            raise ValueError(
                f"policy {self.policy.name!r} needs per-round state "
                "telemetry; pass telemetry_fn to rounds()")
        depth = self.prefetch if prefetch is None else int(prefetch)
        pool: queue.SimpleQueue = queue.SimpleQueue()  # free slab sets

        def build(r: int) -> dict:
            try:
                self._lent.slabs = pool.get_nowait()
            except queue.Empty:
                pass  # none free yet: build allocates a fresh set
            try:
                if needs_state:
                    return self.build(r, telemetry_fn())
                return self.build(r)
            finally:
                self._lent.slabs = None

        if needs_state or depth <= 0:
            for r in range(start, stop):
                host_batch = build(r)
                dev = self.put(host_batch, r)
                yield r, dev
                _recycle(pool, host_batch, dev)
            return

        q: queue.Queue = queue.Queue(maxsize=depth)
        stop_evt = threading.Event()

        def _feed(item) -> bool:
            while not stop_evt.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for r in range(start, stop):
                    if stop_evt.is_set() or not _feed((r, build(r))):
                        return
                _feed(_SENTINEL)
            except BaseException as e:  # surface build errors to the
                _feed(e)  # consumer instead of hanging it on q.get()

        t = threading.Thread(target=worker, daemon=True,
                             name="federated-batcher-prefetch")
        t.start()
        try:
            r = start
            while True:
                with _span("FederatedBatcher.wait", r):
                    t0 = time.perf_counter()
                    item = q.get()
                    self.stall_seconds += time.perf_counter() - t0
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                r, host_batch = item
                dev = self.put(host_batch, r)
                yield r, dev
                _recycle(pool, host_batch, dev)
                r += 1
        finally:
            stop_evt.set()

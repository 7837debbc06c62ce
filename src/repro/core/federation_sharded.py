"""BlendFL round as ONE SPMD program — the TPU-pod expression of Alg. 1.

Hardware adaptation (DESIGN.md §2): the paper's federation is N hospital
GPU boxes + an RPC parameter server. On a TPU pod we map:

    client k            ->  slice k of the mesh "data" axis (stacked
                            client models: every leaf gains a leading C
                            axis sharded over "data"; large hidden dims
                            shard over "model")
    feature upload      ->  all-gather of latent h over the client axis
                            (the alignment gather below; its transpose is
                            the gradient return, from plain autodiff)
    weight upload +     ->  masked weighted reduction over the client
    BlendAvg + broadcast    axis: blended = sum_k omega_k * W_k, lowered
                            by XLA to an all-reduce; the result is already
                            resident on every slice, so the "broadcast
                            back" of Alg. 1 line 32 is free.

Architecture: the four phases are NOT implemented here — they are the
shared stacked-client phase functions from ``repro.core.engine``
(``make_phase_fns``), the same math the in-host ``federation.Federation``
drives. This module only adapts them to the SPMD batch layout (uniform
per-client row counts -> all-ones masks; the PSI alignment arrives as the
``perm_b`` gather) and composes them into one jittable ``round_fn``. The
optimizer is pluggable via ``ShardedFedSpec.optimizer`` ("sgd"|"adamw");
stacked per-client optimizer state shards and threads through the round
inside the state dict.

BlendAvg's validation scoring runs as a vmapped evaluation of all stacked
client models on a replicated validation shard. Inside the SPMD program
the score is the (negative) validation LOSS: a monotone on-device
surrogate for the paper's AUROC (rank statistics don't belong in the hot
aggregation path; the in-host federation.py uses real AUROC). The blend
uses the engine's "reduce" formulation here — the same Eq. 11 the in-host
path runs through the Pallas ``blend_params`` kernel, but expressed as a
weighted reduction over the client axis so GSPMD lowers it to the masked
all-reduce pictured above (a Pallas custom call has no partition rule and
would force an all-gather of every client model).

Partial participation (``ShardedFedSpec.n_sampled`` = K > 0): the round
becomes the K-of-C sampled, staleness-weighted async round. The host (or
an outer loop) draws K client ids into the ``sampled`` batch vector; the
round gathers those rows of the stacked models/opt moments
(``engine.sample_clients`` — a static-shape gather, so the round still
compiles once across subsets), trains the phases at leading axis K,
aggregates over the K candidates with omegas damped by each candidate's
staleness (``round - 1 - last_round[sampled]`` — non-sampled clients are
simply absent from the blend, masked like empty batches), and scatters
the broadcast back to the participants only. ``last_round``/``round``
int vectors thread through the state dict alongside the opt moments.
WHICH K ids arrive is the host's choice: ``ShardedFedSpec.policy`` names
a ``repro.core.schedule`` participation policy fed by the ``sched``
telemetry block (omega EMA / participation counts / last_round mirror)
the round maintains in its state — the ids stay data, so every policy
shares this one compiled program.

Everything below is pure jnp under jit — sharding in_shardings do the
distribution; no host round-trips inside a federated round.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import aggregate as strategies
from repro.core import codec as wire
from repro.core import schedule
from repro.core import state as rstate
from repro.core.encoders import EncoderConfig
from repro.core.engine import (
    CLIENT_GROUPS,
    EngineConfig,
    make_phase_fns,
    stack_with,
)


@dataclasses.dataclass(frozen=True)
class ShardedFedSpec:
    """Static description of the sharded federation workload."""

    n_clients: int = 16
    d_hidden: int = 1024
    n_layers: int = 2
    seq_a: int = 64
    feat_a: int = 128
    seq_b: int = 64
    feat_b: int = 128
    out_dim: int = 25
    kind: str = "multilabel"
    n_partial: int = 512  # per client, per modality
    n_frag: int = 512  # per client (aligned cross-client rows)
    n_paired: int = 512  # per client
    n_val: int = 1024  # replicated server validation set
    # §Perf C.1: BlendAvg only needs the val set to RANK models; scoring
    # all C client models on the full set dominates the round's HBM bytes
    # (measured ~75%). Score on a fixed subsample instead.
    n_val_score: int = 0  # 0 = full n_val
    lr: float = 1e-3
    optimizer: str = "sgd"  # sgd | adamw
    weight_decay: float = 0.0  # adamw only
    schedule: str = "constant"  # constant | cosine
    total_steps: int = 0  # client cosine horizon (optimizer steps)
    # The server g_M^v head steps once per round, not once per client
    # minibatch — under a schedule it needs its own horizon (threaded to
    # EngineConfig.server_total_steps, which selects fns.srv_opt).
    server_total_steps: int = 0
    # Partial participation: K-of-C sampled async rounds. 0 = every
    # client trains every round.
    n_sampled: int = 0
    staleness_exp: float = 0.5  # async omega damping (1+s)^-a
    # Which K clients participate each sampled round — a host-side
    # ``repro.core.schedule`` policy fed by the ``sched`` telemetry block
    # this round threads through its state. The ids stay DATA (they feed
    # the same static-shape gathers), so the policy choice never
    # recompiles anything. "uniform" reproduces the pre-scheduler
    # sampled round bit-exactly.
    policy: str = "uniform"
    ema_beta: float = 0.9  # omega-EMA telemetry decay (schedule.ema_update)
    # "reduce" so the blend lowers to the masked all-reduce over the
    # sharded client axis (a Pallas custom call would force an all-gather
    # of every client model — see EngineConfig.blend).
    blend: str = "reduce"  # reduce | pallas
    # Wire codec for the simulated round traffic (candidate uplink +
    # broadcast downlink deltas, with error-feedback residuals in round
    # state). "none" = uncompressed fp32; see ``repro.core.codec``.
    codec: str = "none"  # none | int8 | topk | int8_topk
    topk_frac: float = 0.25  # entries kept per leaf by sparsifying codecs
    # Aggregation strategy (``repro.core.aggregate``): blendavg keeps the
    # Eq. 9-11 scored blend; fedavg/fedprox weight candidates by data
    # volume (fedprox additionally pulls every client step toward its
    # round-start anchor with ``fedprox_mu``); scaffold corrects client
    # grads with control variates threaded through round state like opt
    # moments and blends participants uniformly. ``server_opt`` applies a
    # server-side FedAdam/momentum step to the blended delta before
    # broadcast and composes with any strategy. Like the codec, the
    # strategy is static round structure: the default adds no state keys
    # and traces no extra ops.
    # blendavg | fedavg | scaffold | fedprox, or a Byzantine-robust
    # reducer: median | trimmed_mean | krum (stateless — no new state
    # keys, old checkpoints stay loadable; ``n_malicious`` is their
    # assumed attacker budget f).
    strategy: str = "blendavg"
    fedprox_mu: float = 0.0
    server_opt: str = "none"  # none | adam | momentum
    server_lr: float = 1.0
    n_malicious: int = 1
    # Gradient-space uplink attackers (``repro.data.scenario`` sign_flip
    # / scale events): when True the batch carries a per-participant
    # ``attack_coef`` (K,) float32 vector — 1.0 honest (exact
    # passthrough), -1.0 sign-flip, SCALE_FACTOR boosted — applied to
    # each candidate's delta vs. its round-start anchor AFTER training
    # (and the SCAFFOLD control update) but BEFORE the uplink codec, so
    # the server decodes exactly what the attacker shipped. The flag is
    # static structure; WHO attacks each round is data.
    attacks: bool = False

    def __post_init__(self):
        if not 0 <= self.n_sampled <= self.n_clients:
            raise ValueError(
                f"n_sampled={self.n_sampled} must be in [0, n_clients="
                f"{self.n_clients}]: a K-of-C sampled round cannot gather "
                "more client rows than the federation stacks (jit gathers "
                "clamp out-of-range ids silently, so this must fail on the "
                "host)")
        f = self.n_malicious
        if self.strategy == "krum" and self.k_round < f + 3:
            raise ValueError(
                f"krum needs at least n_malicious + 3 = {f + 3} candidates "
                f"per round to score n - f - 2 neighbors, got K="
                f"{self.k_round}")
        if self.strategy == "trimmed_mean" and self.k_round < 2 * f + 1:
            raise ValueError(
                f"trimmed_mean needs at least 2 * n_malicious + 1 = "
                f"{2 * f + 1} candidates per round, got K={self.k_round}")

    @property
    def ecfg(self) -> EncoderConfig:
        return EncoderConfig(d_hidden=self.d_hidden, n_layers=self.n_layers,
                             enc_type="mlp")

    @property
    def k_round(self) -> int:
        """Clients that train per round (leading axis of the batch)."""
        return self.n_sampled or self.n_clients

    @property
    def engine_cfg(self) -> EngineConfig:
        return EngineConfig(ecfg=self.ecfg, kind=self.kind,
                            optimizer=self.optimizer, lr=self.lr,
                            weight_decay=self.weight_decay,
                            schedule=self.schedule, total_steps=self.total_steps,
                            server_total_steps=self.server_total_steps,
                            staleness_exp=self.staleness_exp, blend=self.blend,
                            codec=wire.make_codec(self.codec, self.topk_frac),
                            strategy=strategies.make_strategy(
                                self.strategy, self.fedprox_mu,
                                self.server_opt, self.server_lr,
                                self.n_malicious))


def init_stacked_models(key, spec: ShardedFedSpec):
    """Stacked client models: every leaf has leading axis C. All clients
    start from the same init (standard FL), so we broadcast one init."""
    from repro.core.encoders import init_client_models
    from repro.data.synthetic import TaskSpec

    tspec = TaskSpec("sharded", spec.kind, spec.out_dim, spec.seq_a, spec.feat_a,
                     spec.seq_b, spec.feat_b)
    base = init_client_models(key, tspec, spec.ecfg)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (spec.n_clients,) + x.shape), base)
    server_gmv = base["g_M"]
    global_models = base
    return stacked, server_gmv, global_models


def init_round_state(key, spec: ShardedFedSpec) -> dict:
    """Full round-state pytree: stacked models + global/server models +
    stacked optimizer state + the async round bookkeeping (``round``
    counter and per-client ``last_round`` sync vector) + the ``sched``
    participation telemetry (omega EMA, participation counts, last_round
    mirror — what the host-side ``repro.core.schedule`` policies read).
    This is what ``make_blendfl_round`` threads; because the telemetry is
    ordinary state leaves, it checkpoints/restores bit-exactly through
    the existing full-round-state path and an adaptive policy resumes on
    the same ids it would have picked uninterrupted. The server head's
    state comes from ``fns.srv_opt`` — the optimizer with the server's
    own schedule horizon (``server_total_steps``), not the clients' — so
    the threaded schedule state matches the optimizer that consumes it in
    ``vfl_step``.

    The block LAYOUT is not spelled out here — it is the round-state
    registry's (``repro.core.state.build_round_state``, byte-identical
    to the historical layout): codec "none" and stateless strategies add
    no keys, so existing checkpoints restore untouched."""
    stacked, server_gmv, global_models = init_stacked_models(key, spec)
    fns = make_phase_fns(spec.engine_cfg)
    return rstate.build_round_state(
        stacked=stacked, server_gmv=server_gmv, global_models=global_models,
        opt_state=fns.opt.init({k: stacked[k] for k in CLIENT_GROUPS}),
        srv_opt_state=fns.srv_opt.init(server_gmv),
        n_clients=spec.n_clients, codec_on=spec.codec != "none",
        scfg=spec.engine_cfg.strategy)


def make_blendfl_round(spec: ShardedFedSpec):
    """Returns round_fn(state, batch) -> (state', metrics).

    state: see ``init_round_state``. batch keys (leading K = per-round
    client axis, = C at full participation, unless noted):
      partial_a (K,Np,Sa,Fa)  partial_ya (K,Np,O)   partial_b / _yb
      frag_a    (K,Nf,Sa,Fa)  frag_y    (K,Nf,O)    frag_b (K,Nf,Sb,Fb)
      perm_b    (K*Nf,) int32 global alignment: row i of gathered h_a
                pairs with row perm_b[i] of gathered h_b (the PSI output)
      sampled   (K,) int32 sampled client ids [n_sampled > 0 only]
      attack_coef (K,) f32 per-participant uplink attack coefficient
                (1 honest / -1 sign-flip / SCALE_FACTOR) [attacks only]
      val_a (Nv,Sa,Fa) val_b (Nv,Sb,Fb) val_y (Nv,O)   [replicated]

    With ``spec.n_sampled`` set, the round gathers the sampled rows of the
    stacked models/opt moments, trains at leading axis K, damps each
    candidate's omega by its staleness, and scatters the broadcast back to
    the participants only (async: non-sampled clients keep stale weights
    and are absent from the blend). The sampled ids are DATA — the round
    compiles once across different subsets of the same K. Like every
    gather index under jit (``perm_b`` included), ids must lie in
    [0, n_clients): out-of-range values clamp silently instead of
    raising, so validate on the host when ids come from untrusted input.

    Each stage that runs traces under a ``jax.named_scope`` (``unimodal``,
    ``vfl``, ``paired``, ``scaffold``, ``forge``, ``codec_uplink``,
    ``aggregate`` with ``score`` and ``blend`` inside under BlendAvg,
    ``server_update``, ``codec_downlink``, ``scatter``), so the
    ``op_name`` of every device op in a profile names its stage.
    """
    fns = make_phase_fns(spec.engine_cfg)
    K = spec.k_round
    scfg = spec.engine_cfg.strategy
    # SCAFFOLD Option-II scaling: optimizer steps each group took this
    # round (encoders step in all three phases; heads in one).
    scaffold_steps = {"f_A": 3.0, "f_B": 3.0, "g_A": 1.0, "g_B": 1.0,
                      "g_M": 1.0}

    def aggregate(models, server_gmv, global_models, batch, staleness):
        """Phase 4 on device: -val-loss scores, then the shared (async)
        BlendAvg over the K participating candidates."""
        val_a, val_b, val_y = batch["val_a"], batch["val_b"], batch["val_y"]
        if spec.n_val_score and spec.n_val_score < spec.n_val:
            val_a = val_a[: spec.n_val_score]
            val_b = val_b[: spec.n_val_score]
            val_y = val_y[: spec.n_val_score]
        ones = jnp.ones(val_y.shape[0], jnp.float32)

        def uni_score(f, g, x):  # higher is better
            return -fns.unimodal_loss(f, g, x, val_y, ones)[0]

        def multi_score(g_m, f_a, f_b):
            return -fns.paired_loss(f_a, f_b, g_m, val_a, val_b, val_y, ones)[0]

        new_global = dict(global_models)
        infos = {}
        for mod, x_val in (("A", val_a), ("B", val_b)):
            with jax.named_scope("score"):
                scores = jax.vmap(lambda f, g: uni_score(f, g, x_val))(
                    models[f"f_{mod}"], models[f"g_{mod}"])
                gscore = uni_score(global_models[f"f_{mod}"],
                                   global_models[f"g_{mod}"], x_val)
            cand = {"f": models[f"f_{mod}"], "g": models[f"g_{mod}"]}
            glob = {"f": global_models[f"f_{mod}"], "g": global_models[f"g_{mod}"]}
            with jax.named_scope("blend"):
                blended, omega, _ = fns.blendavg_update(
                    glob, cand, scores, gscore, staleness=staleness)
            new_global[f"f_{mod}"], new_global[f"g_{mod}"] = blended["f"], blended["g"]
            infos[f"omega_{mod}"] = omega

        # multimodal: K participating heads + the server's g_M^v (Eq. 8);
        # the server head trains every round, so its staleness is 0
        cand = stack_with(models["g_M"], server_gmv)
        stale_m = (None if staleness is None
                   else jnp.concatenate([staleness, jnp.zeros(1, jnp.float32)]))
        with jax.named_scope("score"):
            scores = jax.vmap(lambda gm: multi_score(
                gm, new_global["f_A"], new_global["f_B"]))(cand)
            gscore = multi_score(global_models["g_M"], new_global["f_A"],
                                 new_global["f_B"])
        with jax.named_scope("blend"):
            new_global["g_M"], infos["omega_M"], _ = fns.blendavg_update(
                global_models["g_M"], cand, scores, gscore, staleness=stale_m)
        return new_global, infos

    def aggregate_weighted(models, server_gmv, global_models, batch):
        """Phase 4 for the score-free strategies: fedavg/fedprox weight
        each candidate by the rows it trained on this round (read off the
        batch masks; the uniform synthetic layout reduces to equal
        weights), scaffold blends participants uniformly (SCAFFOLD's
        x + (K/C-scaled) mean(y_i - x) server step at eta_g = 1). The
        multimodal blend stacks the server's g_M^v as candidate K with
        the total live aligned rows as its volume — it trained on every
        client's fragmented rows. Staleness damping is a BlendAvg scoring
        concept and does not apply here.

        The Byzantine-robust strategies route the same candidates
        through ``fns.robust_update`` instead of the weighted average:
        krum masks the volume weights down to the multi-Krum survivors
        (so at n_malicious = 0 it IS this function's fedavg path
        bit-for-bit), median / trimmed_mean reduce coordinate-wise. The
        server's g_M^v rides as an extra candidate for the M head there
        too — an honest anchor the distance scores can lean on."""
        if "partial_ma" in batch:
            na = jnp.sum(batch["partial_ma"], axis=1)
            nb = jnp.sum(batch["partial_mb"], axis=1)
        else:
            na = jnp.full((K,), float(spec.n_partial))
            nb = jnp.full((K,), float(spec.n_partial))
        n_pair = (jnp.sum(batch["paired_m"], axis=1) if "paired_m" in batch
                  else jnp.full((K,), float(spec.n_paired)))
        n_frag = (jnp.sum(batch["frag_w"].reshape(K, spec.n_frag), axis=1)
                  if "frag_w" in batch
                  else jnp.full((K,), float(spec.n_frag)))
        if scfg.control:
            w_cli = jnp.ones((K,), jnp.float32)
            w_m = jnp.ones((K + 1,), jnp.float32)
        else:
            w_cli = na + nb + n_pair + n_frag
            w_m = jnp.concatenate([n_pair, jnp.sum(n_frag)[None]])

        new_global = dict(global_models)
        infos = {}
        for mod in ("A", "B"):
            cand = {"f": models[f"f_{mod}"], "g": models[f"g_{mod}"]}
            glob = {"f": global_models[f"f_{mod}"],
                    "g": global_models[f"g_{mod}"]}
            if scfg.robust:
                blended, om = fns.robust_update(glob, cand, w_cli)
            else:
                blended = fns.fedavg_update(glob, cand, w_cli)
                # normalized weights double as the sched telemetry
                # omegas, so the participation policies see the same
                # [0, 1] mass they see under blendavg
                om = w_cli / jnp.maximum(jnp.sum(w_cli), 1e-12)
            new_global[f"f_{mod}"] = blended["f"]
            new_global[f"g_{mod}"] = blended["g"]
            infos[f"omega_{mod}"] = om
        cand = stack_with(models["g_M"], server_gmv)
        if scfg.robust:
            new_global["g_M"], infos["omega_M"] = fns.robust_update(
                global_models["g_M"], cand, w_m)
        else:
            new_global["g_M"] = fns.fedavg_update(global_models["g_M"],
                                                  cand, w_m)
            infos["omega_M"] = w_m / jnp.maximum(jnp.sum(w_m), 1e-12)
        return new_global, infos

    def round_fn(state, batch):
        # ONE registry-routed gather covers every block: stacked leaves
        # come down to the K sampled rows ((C,...) -> (K,...), ids as
        # data), global leaves pass through. Full participation (idx
        # None) is the identity.
        idx = batch["sampled"] if spec.n_sampled else None
        sub = rstate.sample(state, idx)
        models, opt_state = sub["models"], sub["opt"]
        staleness = (jnp.maximum(state["round"] - 1 - sub["last_round"], 0)
                     .astype(jnp.float32) if spec.n_sampled else None)
        server_gmv, srv_state = sub["server_gmv"], sub["srv_opt"]
        codec_on = spec.codec != "none"
        if codec_on:
            # uplink base: the weights each participant starts this
            # round from (its delta is what crosses the wire), plus its
            # error-feedback residual rows
            base = models
            resid_up = sub["codec"]["resid_up"]
        # strategy block for the phase functions: each participant's
        # round-start weights anchor the FedProx pull; SCAFFOLD's c_local
        # rows arrive gathered like opt moments
        anchor = models
        strat = None
        if scfg.control:
            c_local = sub["strat"]["c_local"]
        if scfg.client_active:
            strat = {}
            if scfg.prox:
                strat["anchor"] = anchor
            if scfg.control:
                strat["c_global"] = state["strat"]["c_global"]
                strat["c_local"] = c_local

        # phase 1: local unimodal training. Ragged federations (the
        # FederatedBatcher) ship real 0/1 row masks; the uniform synthetic
        # path omits them and every padded row is live.
        with jax.named_scope("unimodal"):
            p1 = {"xa": batch["partial_a"], "ya": batch["partial_ya"],
                  "ma": batch.get("partial_ma", jnp.ones(
                      batch["partial_ya"].shape[:2], jnp.float32)),
                  "xb": batch["partial_b"], "yb": batch["partial_yb"],
                  "mb": batch.get("partial_mb", jnp.ones(
                      batch["partial_yb"].shape[:2], jnp.float32))}
            models, opt_state, i1 = fns.unimodal_step(models, opt_state, p1,
                                                      strat)
            # average over clients that actually held rows (all of them in
            # the uniform layout, where this reduces to the plain mean)
            wa = (i1["n_a"] > 0).astype(jnp.float32)
            wb = (i1["n_b"] > 0).astype(jnp.float32)
            loss_uni = ((jnp.sum(i1["loss_a"] * wa)
                         + jnp.sum(i1["loss_b"] * wb))
                        / jnp.maximum(jnp.sum(wa) + jnp.sum(wb), 1.0))

        # phase 2: split (VFL) training; identity gather on the a side,
        # the PSI permutation on the b side. ``frag_w`` zero-weights
        # padded/unmatched alignment rows; ``frag_part_*`` excludes
        # clients with no live aligned rows from the param update.
        with jax.named_scope("vfl"):
            p2 = {"xa": batch["frag_a"], "xb": batch["frag_b"],
                  "gather_a": jnp.arange(K * spec.n_frag, dtype=jnp.int32),
                  "gather_b": batch["perm_b"],
                  "y": batch["frag_y"].reshape(K * spec.n_frag, -1),
                  "w": batch.get("frag_w"),
                  "part_a": batch.get("frag_part_a"),
                  "part_b": batch.get("frag_part_b")}
            models, server_gmv, opt_state, srv_state, loss_vfl = fns.vfl_step(
                models, server_gmv, opt_state, srv_state, p2, strat)

        # phase 3: local multimodal training on paired rows
        with jax.named_scope("paired"):
            p3 = {"xa": batch["paired_a"], "xb": batch["paired_b"],
                  "y": batch["paired_y"],
                  "m": batch.get("paired_m", jnp.ones(
                      batch["paired_y"].shape[:2], jnp.float32))}
            models, opt_state, i3 = fns.paired_step(models, opt_state, p3,
                                                    strat)
            wp = (i3["n"] > 0).astype(jnp.float32)
            loss_paired = (jnp.sum(i3["loss"] * wp)
                           / jnp.maximum(jnp.sum(wp), 1.0))

        # SCAFFOLD control-variate round update on the TRUE trained
        # weights (Option II runs server-side on what the clients really
        # computed — before the lossy uplink codec touches the
        # candidates), scaled by the participation fraction K/C
        if scfg.control:
            with jax.named_scope("scaffold"):
                new_cg, new_cl = fns.scaffold_round(
                    state["strat"]["c_global"], c_local, anchor, models,
                    scaffold_steps, K / spec.n_clients)

        # gradient-space uplink attackers: each participant ships
        # anchor + coef * (trained - anchor). coef is DATA (the attacker
        # set changes round to round without recompiling); an exact
        # where-passthrough keeps honest rows (coef == 1) bit-identical
        # to the unattacked round. Sits after the SCAFFOLD update (the
        # true training still happened client-side) and before the
        # uplink codec (the server decodes what the attacker shipped).
        if spec.attacks:
            coef = batch["attack_coef"].astype(jnp.float32)

            def forge(t, a):
                c = coef.reshape((K,) + (1,) * (t.ndim - 1))
                forged = (a.astype(jnp.float32)
                          + c * (t.astype(jnp.float32)
                                 - a.astype(jnp.float32))).astype(t.dtype)
                return jnp.where(c == 1.0, t, forged)

            with jax.named_scope("forge"):
                models = jax.tree.map(forge, models, anchor)

        # wire codec, uplink leg: the trained weights become candidates
        # only after the lossy client->server round-trip — aggregation
        # scores and blends what the server would actually receive
        if codec_on:
            with jax.named_scope("codec_uplink"):
                models, resid_up = fns.codec_uplink(models, base, resid_up)

        # phase 4: aggregation + broadcast. BlendAvg scores candidates on
        # the replicated val shard (Eq. 9-11); the score-free strategies
        # blend by data volume / uniformly. Full participation: the
        # broadcast is free under SPMD (the reduction leaves the blend
        # resident on every slice). Sampled: participants-only scatter —
        # stragglers keep their stale rows; the trained weights only
        # mattered as candidates, while opt moments ride home per client.
        with jax.named_scope("aggregate"):
            if scfg.score_based:
                new_global, infos = aggregate(
                    models, server_gmv, global_models=state["global_models"],
                    batch=batch, staleness=staleness)
            else:
                new_global, infos = aggregate_weighted(
                    models, server_gmv, global_models=state["global_models"],
                    batch=batch)
        # server-side optimizer on the blended delta, before anything is
        # broadcast (clients — and the downlink codec — see the adjusted
        # global, and the server's g_M^v re-seeds from it)
        if scfg.server_opt != "none":
            with jax.named_scope("server_update"):
                new_global, srv_moments = fns.server_update(
                    state["strat"]["srv"], new_global, state["global_models"])
        # wire codec, downlink leg: clients adopt the blend as decoded
        # from the broadcast delta. The server's own g_M^v head never
        # crosses a wire — it re-seeds from the TRUE blend below.
        srv_gmv_true = new_global["g_M"]
        if codec_on:
            with jax.named_scope("codec_downlink"):
                new_global, resid_down = fns.codec_downlink(
                    new_global, state["global_models"], state["codec"]["resid_down"])
        with jax.named_scope("scatter"):
            bcast = dict(fns.broadcast(
                {k: new_global[k] for k in CLIENT_GROUPS}, K))
            # per-participant sync stamp: K rows in a sampled round (the
            # registry scatters them to the drawn slots), the whole vector at
            # full participation (idx None replaces wholesale)
            last_round = (jnp.full((K,), state["round"], jnp.int32)
                          if spec.n_sampled
                          else jnp.full_like(state["last_round"], state["round"]))

            # participation telemetry for the host-side scheduler: this
            # round's per-client omega (mean over the three heads' Eq. 10
            # weights; omega_M's trailing server-head slot excluded) folds
            # into the EMA at the participants' slots only, mirroring the
            # async broadcast. Pure jnp — the policy choice is host-side, so
            # the compiled round is identical across policies. The update
            # math runs on the gathered rows; WHERE the rows land is the
            # registry scatter's job.
            cli_omega = (infos["omega_A"] + infos["omega_B"]
                         + infos["omega_M"][: K]) / 3.0
            new_sched = {
                "omega_ema": schedule.ema_update(sub["sched"]["omega_ema"],
                                                 cli_omega, spec.ema_beta),
                "part_count": sub["sched"]["part_count"] + 1,
                "last_round": last_round,
            }

            # ONE registry-routed scatter writes the round back: stacked
            # rows land at the sampled slots, global blocks replace.
            updates = {"models": bcast, "server_gmv": srv_gmv_true,
                       "global_models": new_global, "opt": opt_state,
                       "srv_opt": srv_state, "last_round": last_round,
                       "round": state["round"] + 1, "sched": new_sched}
            if codec_on:
                updates["codec"] = {"resid_up": resid_up,
                                    "resid_down": resid_down}
            if scfg.stateful:
                new_strat = {}
                if scfg.control:
                    new_strat["c_global"] = new_cg
                    new_strat["c_local"] = new_cl
                if scfg.server_opt != "none":
                    new_strat["srv"] = srv_moments
                updates["strat"] = new_strat
            state = rstate.scatter(state, updates, idx)
        metrics = dict(loss_uni=loss_uni, loss_vfl=loss_vfl,
                       loss_paired=loss_paired, **infos)
        return state, metrics

    return round_fn


def batch_specs(spec: ShardedFedSpec, ragged: bool = False):
    """ShapeDtypeStructs for one federated round's inputs (dry-run).
    Training arrays carry the per-round client axis K (= C at full
    participation); a sampled round additionally takes the K sampled
    client ids. ``ragged=True`` adds the heterogeneous-row-count keys the
    ``FederatedBatcher`` emits: per-row 0/1 masks for phases 1/3, the
    per-aligned-row weight vector for phase 2, and the per-client VFL
    participation flags."""
    f32 = jnp.float32
    K = spec.k_round
    sds = jax.ShapeDtypeStruct
    specs = {
        "partial_a": sds((K, spec.n_partial, spec.seq_a, spec.feat_a), f32),
        "partial_ya": sds((K, spec.n_partial, spec.out_dim), f32),
        "partial_b": sds((K, spec.n_partial, spec.seq_b, spec.feat_b), f32),
        "partial_yb": sds((K, spec.n_partial, spec.out_dim), f32),
        "frag_a": sds((K, spec.n_frag, spec.seq_a, spec.feat_a), f32),
        "frag_b": sds((K, spec.n_frag, spec.seq_b, spec.feat_b), f32),
        "frag_y": sds((K, spec.n_frag, spec.out_dim), f32),
        "perm_b": sds((K * spec.n_frag,), jnp.int32),
        "paired_a": sds((K, spec.n_paired, spec.seq_a, spec.feat_a), f32),
        "paired_b": sds((K, spec.n_paired, spec.seq_b, spec.feat_b), f32),
        "paired_y": sds((K, spec.n_paired, spec.out_dim), f32),
        "val_a": sds((spec.n_val, spec.seq_a, spec.feat_a), f32),
        "val_b": sds((spec.n_val, spec.seq_b, spec.feat_b), f32),
        "val_y": sds((spec.n_val, spec.out_dim), f32),
    }
    if ragged:
        specs.update({
            "partial_ma": sds((K, spec.n_partial), f32),
            "partial_mb": sds((K, spec.n_partial), f32),
            "frag_w": sds((K * spec.n_frag,), f32),
            "frag_part_a": sds((K,), jnp.bool_),
            "frag_part_b": sds((K,), jnp.bool_),
            "paired_m": sds((K, spec.n_paired), f32),
        })
    if spec.n_sampled:
        specs["sampled"] = sds((K,), jnp.int32)
    if spec.attacks:
        specs["attack_coef"] = sds((K,), f32)
    return specs

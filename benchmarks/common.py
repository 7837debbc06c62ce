"""Shared experiment driver for the paper's tables/figures."""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

# the record helpers live in a JAX-free module, so that a parent process
# that only spawns measuring children never imports JAX
from benchmarks.emit import RESULTS_DIR, max_rss_mb, write_bench_json  # noqa: F401

from repro.core.baselines import BASELINES
from repro.core.encoders import EncoderConfig
from repro.core.federation import FedConfig, Federation, evaluate_global
from repro.core.partitioner import partition
from repro.data.synthetic import make_task, train_val_test
from repro.metrics import auprc, auroc, bootstrap_ci


@dataclasses.dataclass
class ExpConfig:
    task: str = "smnist"
    n_train: int = 500
    n_val: int = 400
    n_test: int = 600
    n_clients: int = 3
    rounds: int = 25
    lr: float = 1e-2
    batch_size: int = 64
    frac_paired: float = 0.4
    frac_fragmented: float = 0.3
    frac_partial: float = 0.3
    dirichlet_alpha: float | None = None  # label-skew (non-IID) if set
    d_hidden: int = 48
    seed: int = 0


def setup(exp: ExpConfig):
    spec = make_task(exp.task)
    tr, va, te = train_val_test(spec, exp.n_train, exp.n_val, exp.n_test,
                                seed=exp.seed)
    clients = partition(tr, exp.n_clients, frac_paired=exp.frac_paired,
                        frac_fragmented=exp.frac_fragmented,
                        frac_partial=exp.frac_partial,
                        dirichlet_alpha=exp.dirichlet_alpha, seed=exp.seed + 1)
    ecfg = EncoderConfig(d_hidden=exp.d_hidden, n_layers=2, enc_type="mlp")
    fcfg = FedConfig(n_clients=exp.n_clients, rounds=exp.rounds, lr=exp.lr,
                     batch_size=exp.batch_size, seed=exp.seed)
    return spec, tr, va, te, clients, ecfg, fcfg


def run_blendfl(exp: ExpConfig, history_test=None, aggregator="blendavg",
                local_epochs=1):
    spec, tr, va, te, clients, ecfg, fcfg = setup(exp)
    fcfg = FedConfig(**{**dataclasses.asdict(fcfg),
                        "aggregator": aggregator, "local_epochs": local_epochs})
    fed = Federation.init(jax.random.PRNGKey(exp.seed), fcfg, spec, ecfg,
                          clients, va)
    history = []
    for r in range(fcfg.rounds):
        fed.round()
        if history_test is not None:
            history.append(dict(evaluate_global(fed, history_test), round=r))
    return evaluate_global(fed, te), history, (fed, te)


def run_baseline(name: str, exp: ExpConfig, history_test=None):
    spec, tr, va, te, clients, ecfg, fcfg = setup(exp)
    return BASELINES[name](jax.random.PRNGKey(exp.seed), spec, ecfg, clients,
                           va, te, fcfg, history_test=history_test)


def scores_with_ci(fed, te):
    """Paper-style 'point (lo, hi)' strings for the global models."""
    from repro.core.encoders import task_scores
    from repro.core.federation import _client_fwd
    from repro.core.encoders import fusion_apply
    from repro.models.common import dense
    import jax.numpy as jnp

    g, ecfg, kind = fed.global_models, fed.ecfg, fed.spec.kind
    h_a = _client_fwd(g["f_A"], jnp.asarray(te.x_a), ecfg=ecfg)
    h_b = _client_fwd(g["f_B"], jnp.asarray(te.x_b), ecfg=ecfg)
    outs = {}
    for name, scores in [
        ("multimodal", task_scores(fusion_apply(g["g_M"], h_a, h_b), kind)),
        ("uni_a", task_scores(dense(g["g_A"], h_a), kind)),
        ("uni_b", task_scores(dense(g["g_B"], h_b), kind)),
    ]:
        s = np.asarray(scores)
        for mname, mfn in (("auroc", auroc), ("auprc", auprc)):
            p, lo, hi = bootstrap_ci(mfn, te.y, s, n_boot=100)
            outs[f"{name}_{mname}"] = f"{p:.3f} ({lo:.3f}, {hi:.3f})"
    return outs


def fmt_row(name: str, res: dict) -> str:
    cols = ["multimodal_auroc", "multimodal_auprc", "uni_a_auroc", "uni_a_auprc",
            "uni_b_auroc", "uni_b_auprc"]
    vals = []
    for c in cols:
        v = res.get(c, float("nan"))
        vals.append(f"{v:.3f}" if isinstance(v, float) else str(v))
    return f"{name:14s} " + " ".join(f"{v:>8s}" for v in vals)


def timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us

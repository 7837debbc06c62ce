"""Whole runs with the timed path broken underneath, the control in the
program's place, and a run without a chip: none of them is correct."""
import jax
import numpy as np
import pytest

from bench import checks, run, serve_cell, train_cell, traffic_gen
from bench.tests.conftest import SEED, tiny


def _state_unchanged(monkeypatch):
    from repro.launch import train_federated as tf
    real = tf.jit_round

    def broken(spec, mesh):
        fn = real(spec, mesh)
        return lambda st, b: (st, fn(st, b)[1])
    monkeypatch.setattr(tf, "jit_round", broken)


def _half_batch(monkeypatch):
    from repro.data.pipeline import FederatedBatcher
    real = FederatedBatcher.build

    def build(self, r, sched=None):
        b = real(self, r, sched)
        cap = self.spec.n_partial
        for k in ("partial_ma", "partial_mb", "paired_m"):
            b[k][:, cap // 2:] = 0.0
        b["frag_w"].reshape(-1, cap)[:, cap // 2:] = 0.0
        return b
    monkeypatch.setattr(FederatedBatcher, "build", build)


def _answer_altered(monkeypatch):
    from repro.core.serving import ServingEngine
    real = ServingEngine._execute

    def execute(self, batch):
        return real(self, batch).at[0, 0].add(0.5)
    monkeypatch.setattr(ServingEngine, "_execute", execute)


@pytest.mark.parametrize("cell,fault", [
    ("mimic_cxr.train", _state_unchanged), ("mimic_cxr.train", _half_batch),
    ("uci_har.train", _state_unchanged), ("uci_har.train", _half_batch),
    ("mimic_cxr.serve", _answer_altered)])
def test_a_broken_timed_path_is_not_correct(harness, monkeypatch, cell, fault):
    fault(monkeypatch)
    rc, res = harness(cell)
    assert rc == 0 and not res["correct"], res["compared"]


@pytest.mark.parametrize("cell", ["mimic_cxr.train", "mimic_cxr.serve"])
def test_control_separates_from_the_program(harness, cache, cell):
    """The reference in three bfloat16 passes (the precision below the
    configuration's ``highest``) in the program's place reads at least
    three times what the program reads on one of the cell's numbers.

    At this size the control's gaps are smaller than at the cell's own
    size, where the chip's readings set the limits (PERF.md, section 2);
    so this test holds the control to the program's readings here."""
    _, res = harness(cell)
    prog = {k: v["value"] for k, v in res["compared"].items()}
    info = tiny(cell)
    cfg, traffic = info["config"], info["traffic"]
    with jax.default_matmul_precision("highest"):
        if traffic["kind"] == "train":
            store = train_cell.datastore.ensure(cfg, cache)
            n = traffic["setup_rounds"]
            ref = train_cell.reference_readings(cfg, store, SEED, n)
            got = train_cell.reference_readings(cfg, store, SEED, n,
                                                precision="high")
            control = checks.train_readings(got, ref)
        else:
            store = train_cell.datastore.ensure(cfg, cache)
            traffic = serve_cell.resolve_traffic(traffic, store)
            pool = train_cell.refdata.StoreFiles(store).val()
            sched = traffic_gen.schedule(traffic, SEED, 1.0, len(pool["val_y"]))
            picks = traffic_gen.check_sample(sched, SEED, 40)
            ref = serve_cell.reference_scores(cfg, traffic, pool, sched, picks,
                                              SEED)
            got = serve_cell.reference_scores(cfg, traffic, pool, sched, picks,
                                              SEED, precision="high")
            control = checks.serve_readings(got, ref)
    assert all(np.isfinite(v) for v in control.values())
    assert any(control[k] >= 3 * prog[k] and control[k] > 0 for k in prog), \
        (control, prog)


def test_without_a_chip_the_run_fails_and_prints_nothing(capsys):
    rc = run.main(["--workload", "mimic_cxr.train", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err

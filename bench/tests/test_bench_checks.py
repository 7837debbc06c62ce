"""The numbers that decide ``correct`` for a training cell, on small
hand-made trees: one client that parts from the reference moves the worst
leaf's gap, not the median client's."""
import numpy as np

from bench import checks


def _state(rng, clients=9):
    mu = {"clients": {"f": {"w": rng.normal(size=(clients, 4, 3)),
                            "b": rng.normal(size=(clients, 3))},
                      "g": {"w": rng.normal(size=(clients, 3, 2))}},
          "server": {"w": rng.normal(size=(3, 2))}}
    glob = {"f": {"w": rng.normal(size=(4, 3)), "b": rng.normal(size=3)},
            "g": {"w": rng.normal(size=(3, 2))}}
    return {"losses": [{"loss_uni": 0.7, "loss_vfl": 0.6, "loss_paired": 0.5}],
            "mu1": mu, "global0": glob, "global": _scaled(glob, 1.1)}


def _scaled(tree, f):
    if isinstance(tree, dict):
        return {k: _scaled(v, f) for k, v in tree.items()}
    return tree * f


def test_one_client_apart_moves_the_worst_leaf_not_the_median_client():
    ref = _state(np.random.default_rng(0))
    prog = _state(np.random.default_rng(0))
    prog["mu1"]["clients"] = _scaled(prog["mu1"]["clients"], 1 + 1e-7)
    base = checks.train_readings(prog, ref)
    assert np.isclose(base["grad_client_gap"], 1e-7, rtol=1e-3)
    prog["mu1"]["clients"]["g"]["w"][4] *= 1.01
    apart = checks.train_readings(prog, ref)
    assert apart["grad_gap"] > 1e-3
    assert np.isclose(apart["grad_client_gap"], base["grad_client_gap"],
                      rtol=1e-6)
    assert apart["loss_gap"] == 0.0


def test_every_client_apart_moves_the_median_client():
    ref = _state(np.random.default_rng(1))
    prog = _state(np.random.default_rng(1))
    prog["mu1"]["clients"]["f"]["b"] *= 1.01
    got = checks.train_readings(prog, ref)
    per_client = checks.client_gaps(prog["mu1"]["clients"],
                                    ref["mu1"]["clients"])
    assert per_client.shape == (9,)
    assert got["grad_client_gap"] == float(np.median(per_client)) > 1e-3

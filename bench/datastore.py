"""The configuration's data set, made once per checkout.

The first run of a cell in a checkout generates the synthetic stand-in at
the configuration's widths (``data.synthetic``), partitions it over the
configuration's clients (``core.partitioner``) and writes it as a
``data.store.ClientStore`` under ``bench/.cache/stores``; every later run
memory-maps that store. Everything is fixed by the configuration's
``data_seed``, not by the run's seed.
"""
from __future__ import annotations

import hashlib
import json
import os

DATA_KEYS = ("task", "kind", "n_labels", "seq_a", "feat_a", "seq_b", "feat_b",
             "noise", "n_clients", "n_train", "n_val", "dirichlet_alpha",
             "data_seed")


def store_path(cfg: dict, cache_dir) -> str:
    key = json.dumps({k: cfg[k] for k in DATA_KEYS}, sort_keys=True)
    tag = hashlib.sha256(key.encode()).hexdigest()[:12]
    return os.path.join(str(cache_dir), "stores", f"{cfg['task']}-{tag}")


def ensure(cfg: dict, cache_dir) -> str:
    """Path of the configuration's client store, written if absent."""
    path = store_path(cfg, cache_dir)
    if os.path.isfile(os.path.join(path, "manifest.json")):
        return path
    from repro.core.partitioner import partition
    from repro.data.store import write_store
    from repro.data.synthetic import TaskSpec, train_val_test
    from repro.launch.train_federated import client_arrays

    task = TaskSpec(cfg["task"], cfg["kind"], cfg["n_labels"], cfg["seq_a"],
                    cfg["feat_a"], cfg["seq_b"], cfg["feat_b"],
                    noise=cfg["noise"])
    tr, va, _ = train_val_test(task, cfg["n_train"], cfg["n_val"], 0,
                               seed=cfg["data_seed"])
    clients = partition(tr, cfg["n_clients"], seed=cfg["data_seed"],
                        dirichlet_alpha=cfg["dirichlet_alpha"])
    del tr
    meta = {"task": cfg["task"], "kind": cfg["kind"],
            "out_dim": cfg["n_labels"], "seq_a": cfg["seq_a"],
            "feat_a": cfg["feat_a"], "seq_b": cfg["seq_b"],
            "feat_b": cfg["feat_b"], "n_train": cfg["n_train"],
            "n_val": cfg["n_val"], "data_seed": cfg["data_seed"],
            "dirichlet_alpha": cfg["dirichlet_alpha"]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    write_store(path, [client_arrays(c) for c in clients],
                {"val_a": va.x_a, "val_b": va.x_b, "val_y": va.y},
                meta=meta, overwrite=True)
    return path


def availability(store_dir: str) -> dict:
    """Share of the store's patients by the route that serves them: both
    modalities at one client (``multimodal``), one modality and no other
    anywhere (``unimodal_A``/``unimodal_B``), or the two split between
    clients (``vfl_fallback``: the EHR side's fragmented rows)."""
    with open(os.path.join(store_dir, "manifest.json")) as f:
        clients = json.load(f)["clients"]

    def rows(key):
        return sum(int(c["keys"][key]["shape"][0]) if key in c["keys"] else 0
                   for c in clients)
    n = {"multimodal": rows("paired_a"), "unimodal_A": rows("partial_a"),
         "unimodal_B": rows("partial_b"), "vfl_fallback": rows("frag_a")}
    total = sum(n.values())
    return {k: v / total for k, v in n.items()}

"""Plain re-derivation of a training round's batch from the client store.

The store is the cell's input data: one directory per federation with a
``manifest.json`` and one ``.npy`` file per (client, key) and for the
server validation set. This module reads those files with numpy alone and
rebuilds round ``r``'s batch by the documented draw rule, so the
reference never takes a batch from the program under test:

- one ``numpy.random.default_rng([seed, r])`` stream per round;
- at full participation every client takes part, in client order;
- per client and slab, in the order partial_a, partial_b, paired_a,
  fragmented a, fragmented b: all rows when they fit the slab's capacity,
  else the first ``cap`` entries of a permutation of the rows; paired_b
  reuses paired_a's rows;
- the fragmented a row with global id ``i`` pairs with the drawn b row
  holding the same id; an a row with no drawn partner weighs 0.
"""
from __future__ import annotations

import json
import os

import numpy as np

SLABS = (("partial_a", "partial_ya", "partial_ma", "a"),
         ("partial_b", "partial_yb", "partial_mb", "b"),
         ("paired_a", "paired_y", "paired_m", "a"))


class StoreFiles:
    """Read-only view of a client store's files."""

    def __init__(self, store_dir: str):
        self.dir = store_dir
        with open(os.path.join(store_dir, "manifest.json")) as f:
            self.manifest = json.load(f)
        self.n_clients = int(self.manifest["n_clients"])

    def rows(self, cid: int, key: str) -> int:
        ent = self.manifest["clients"][cid]["keys"].get(key)
        return 0 if ent is None else int(ent["shape"][0])

    def take(self, cid: int, key: str, sel: np.ndarray) -> np.ndarray:
        path = os.path.join(self.dir, f"client_{cid:05d}", key + ".npy")
        return np.asarray(np.load(path, mmap_mode="r")[sel])

    def val(self) -> dict:
        return {k: np.load(os.path.join(self.dir, "val", k + ".npy"))
                for k in ("val_a", "val_b", "val_y")}


def _draw(rng, avail: int, cap: int) -> np.ndarray:
    if avail <= cap:
        return np.arange(avail)
    return rng.permutation(avail)[:cap]


def round_batch(store: StoreFiles, dims: dict, seed: int, r: int,
                with_x: bool = True) -> dict:
    """Round ``r``'s batch as numpy arrays. ``dims`` holds cap, seq_a,
    feat_a, seq_b, feat_b, out_dim. ``with_x=False`` skips the feature
    and label reads: masks, weights and alignment only."""
    rng = np.random.default_rng([int(seed), int(r)])
    C, cap, out = store.n_clients, dims["cap"], dims["out_dim"]
    shape = {"a": (dims["seq_a"], dims["feat_a"]),
             "b": (dims["seq_b"], dims["feat_b"])}
    batch = {}
    paired_sel = []
    for xk, yk, mk, side in SLABS:
        x = np.zeros((C, cap) + shape[side], np.float32) if with_x else None
        y = np.zeros((C, cap, out), np.float32)
        m = np.zeros((C, cap), np.float32)
        for c in range(C):
            sel = _draw(rng, store.rows(c, xk), cap)
            if xk == "paired_a":
                paired_sel.append(sel)
            n = len(sel)
            if n:
                if with_x:
                    x[c, :n] = store.take(c, xk, sel)
                    y[c, :n] = store.take(c, yk, sel)
                m[c, :n] = 1.0
        batch[xk], batch[yk], batch[mk] = x, y, m
    if with_x:
        xb = np.zeros((C, cap) + shape["b"], np.float32)
        for c, sel in enumerate(paired_sel):
            if len(sel):
                xb[c, :len(sel)] = store.take(c, "paired_b", sel)
        batch["paired_b"] = xb

    fa = np.zeros((C, cap) + shape["a"], np.float32) if with_x else None
    fb = np.zeros((C, cap) + shape["b"], np.float32) if with_x else None
    fy = np.zeros((C * cap, out), np.float32)
    b_pos = {}  # global id -> flat position of its drawn b row
    a_ids = [None] * C
    for c in range(C):
        sel_a = _draw(rng, store.rows(c, "frag_a"), cap)
        sel_b = _draw(rng, store.rows(c, "frag_b"), cap)
        if len(sel_a):
            if with_x:
                fa[c, :len(sel_a)] = store.take(c, "frag_a", sel_a)
                fy[c * cap:c * cap + len(sel_a)] = store.take(c, "frag_y",
                                                              sel_a)
            a_ids[c] = store.take(c, "frag_ids_a", sel_a)
        if len(sel_b):
            if with_x:
                fb[c, :len(sel_b)] = store.take(c, "frag_b", sel_b)
            for j, gid in enumerate(store.take(c, "frag_ids_b", sel_b)):
                b_pos[int(gid)] = c * cap + j
    perm = np.zeros(C * cap, np.int32)
    w = np.zeros(C * cap, np.float32)
    for c in range(C):
        for j, gid in enumerate([] if a_ids[c] is None else a_ids[c]):
            pos = b_pos.get(int(gid))
            if pos is not None:
                perm[c * cap + j] = pos
                w[c * cap + j] = 1.0
    fy[w == 0] = 0.0
    part_a = w.reshape(C, cap).sum(1) > 0
    part_b = np.zeros(C, bool)
    part_b[perm[w > 0] // cap] = True
    batch.update(frag_a=fa, frag_b=fb, frag_y=fy.reshape(C, cap, out),
                 perm_b=perm, frag_w=w, part_a=part_a, part_b=part_b)
    return batch


def live_rows(batch: dict) -> dict:
    """Live row counts of a round batch: per-client slabs and aligned
    pairs (what the FLOP counter charges)."""
    return {"partial_a": batch["partial_ma"].sum(1),
            "partial_b": batch["partial_mb"].sum(1),
            "paired": batch["paired_m"].sum(1),
            "aligned": float(batch["frag_w"].sum())}

"""The numbers that decide ``correct``, each from the program's output and
the reference's."""
from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], prefix + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaves(v, prefix + (str(i),))
    else:
        yield "/".join(prefix), np.asarray(tree, np.float64)


def norm_gaps(prog, ref, keep=None) -> dict:
    """Per leaf: |‖prog‖ − ‖ref‖| over the larger of the reference leaf's
    norm and the median reference leaf norm. ``keep`` names the leaves to
    judge (all when None)."""
    p = dict(_leaves(prog))
    r = dict(_leaves(ref))
    if set(p) != set(r):
        raise ValueError(f"leaf sets differ: {sorted(set(p) ^ set(r))}")
    names = sorted(r) if keep is None else sorted(keep)
    rn = {k: float(np.linalg.norm(r[k])) for k in names}
    med = float(np.median(list(rn.values())))
    return {k: abs(float(np.linalg.norm(p[k])) - rn[k]) / max(rn[k], med, 1e-30)
            for k in names}


def moving_leaves(mu_ref, frac: float = 1e-3) -> set:
    """Leaves whose first-round gradient moment is at least ``frac`` of the
    median leaf's: the others move under Adam by round-off alone."""
    n = {k: float(np.linalg.norm(v)) for k, v in _leaves(mu_ref)}
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= frac * med}


def _sub(a, b):
    if isinstance(a, dict):
        return {k: _sub(a[k], b[k]) for k in a}
    if isinstance(a, (list, tuple)):
        return [_sub(x, y) for x, y in zip(a, b, strict=True)]
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def client_gaps(prog, ref) -> np.ndarray:
    """Per client: the worst leaf's norm gap within that client's slice of
    the stacked client leaves, over the larger of the leaf's and the
    client's median leaf's reference norm."""
    p = dict(_leaves(prog))
    r = dict(_leaves(ref))
    names = sorted(r)
    for k in names:
        if p[k].shape != r[k].shape:
            raise ValueError(f"{k}: shape {p[k].shape} against {r[k].shape}")
    out = []
    for c in range(r[names[0]].shape[0]):
        rn = {k: float(np.linalg.norm(r[k][c])) for k in names}
        med = float(np.median(list(rn.values())))
        out.append(max(abs(float(np.linalg.norm(p[k][c])) - rn[k])
                       / max(rn[k], med, 1e-30) for k in names))
    return np.asarray(out)


def train_readings(prog: dict, ref: dict) -> dict:
    """prog/ref: ``losses`` (per round dicts), ``mu1`` ({clients, server}
    first moments after round 1), ``global0`` and ``global`` (global
    models before round 1 and after the last round).

    ``grad_gap`` is the worst leaf's gap of first-moment norms. Round 1
    takes three Adam steps; the first one's normalised update turns a
    gradient element whose sign rounding decides into a step of the full
    learning rate, so on some seeds one client's later gradients part
    from the reference by far more than rounding. ``grad_client_gap`` is
    the median client's worst-leaf gap, which that client does not
    move."""
    loss_gap = 0.0
    for lp, lr in zip(prog["losses"], ref["losses"], strict=True):
        for k, v in lr.items():
            loss_gap = max(loss_gap, abs(lp[k] - v) / max(abs(v), 1e-30))
    grad = norm_gaps(prog["mu1"], ref["mu1"])
    keep = moving_leaves(ref["mu1"]["clients"])
    change = norm_gaps(_sub(prog["global"], prog["global0"]),
                       _sub(ref["global"], ref["global0"]), keep=keep)
    return {"loss_gap": loss_gap, "grad_gap": max(grad.values()),
            "grad_client_gap": float(np.median(client_gaps(
                prog["mu1"]["clients"], ref["mu1"]["clients"]))),
            "change_gap": max(change.values())}


def worst_grad_leaves(prog: dict, ref: dict, n: int = 3) -> list:
    """The ``n`` leaves of the first moments with the widest norm gaps, as
    [leaf, gap, reference norm] (which leaf sets ``grad_gap``), then the
    ``n`` clients with the widest worst-leaf gaps, as [client, gap]."""
    grad = norm_gaps(prog["mu1"], ref["mu1"])
    r = dict(_leaves(ref["mu1"]))
    top = sorted(grad, key=grad.get, reverse=True)[:n]
    cg = client_gaps(prog["mu1"]["clients"], ref["mu1"]["clients"])
    worst = np.argsort(cg)[::-1][:n]
    return ([[k, grad[k], float(np.linalg.norm(r[k]))] for k in top]
            + [[f"client {int(c)}", float(cg[c])] for c in worst])


def serve_readings(prog: dict, ref: dict) -> dict:
    """prog/ref: {route: (rows, n_labels) scores} over the sampled rows.
    ``local_gap``: widest score gap on the routes served locally.
    ``vfl_median_gap``: median over VFL rows of the row's widest gap (the
    codec's rounding and top-k decisions make single VFL entries jump
    under any change of arithmetic; the median row does not)."""
    local = [np.max(np.abs(prog[r] - ref[r])) for r in prog
             if r != "vfl_fallback" and len(ref[r])]
    out = {"local_gap": float(max(local)) if local else 0.0}
    if len(ref.get("vfl_fallback", ())):
        row = np.max(np.abs(prog["vfl_fallback"] - ref["vfl_fallback"]), axis=1)
        out["vfl_median_gap"] = float(np.median(row))
    return out

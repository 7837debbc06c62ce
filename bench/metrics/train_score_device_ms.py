"""Device time of BlendAvg's candidate scoring per window round, in ms:
the union of the ops whose named scope lies under ``aggregate/score``
(``core/federation_sharded.make_blendfl_round``; ``run.op_scopes``),
averaged over the devices. A round that scores no candidates reads
nothing."""
from bench.traces import _union, scope_of

SCOPE = "aggregate/score"


def _scoring(scope) -> bool:
    return scope is not None and (scope == SCOPE or scope.startswith(SCOPE + "/"))


def read(run):
    scopes = getattr(run, "op_scopes", None)
    ops = getattr(run, "ops", None)
    if not scopes or not ops or not run.rounds:
        return None
    busy_ns = sum(sum(e - s for s, e in _union(
        [(s, e) for n, s, e in evs if _scoring(scope_of(n, scopes))]))
        for evs in ops.values())
    if not busy_ns:
        return None
    return busy_ns / len(ops) / run.rounds / 1e6

"""Host time of the transfer of a round's batch to the device, per round
of the traced window, in ms: the union of the ``FederatedBatcher.put``
spans (``FederatedBatcher.put`` puts one round's batch)."""
from bench.traces import _union

PUT = "FederatedBatcher.put"


def read(run):
    puts = [(s, e) for n, s, e in getattr(run, "spans", None) or []
            if n == PUT]
    if not puts or not run.rounds:
        return None
    return sum(e - s for s, e in _union(puts)) / 1e6 / run.rounds

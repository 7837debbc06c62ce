"""Share of the traced training window in which device 0 ran no operation
while the round loop waited for a staged batch, in percent: the exact
overlap of the device's idle time with the program's
``FederatedBatcher.wait`` spans (``FederatedBatcher.rounds``), not the
midpoint charging of the breakdown's idle gaps. A program without those
spans reads nothing."""
from bench.traces import _union

WAIT = "FederatedBatcher.wait"


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    t = getattr(run, "trace", None)
    ops = getattr(run, "ops", None)
    waits = _union([(s, e) for n, s, e in getattr(run, "spans", None) or []
                    if n == WAIT])
    if not t or not t["n_ops"] or not ops or not waits:
        return None
    busy = _union([(s, e) for _, s, e in ops[sorted(ops)[0]]])
    idle_ns = sum(e - s for s, e in waits) - _overlap(waits, busy)
    return idle_ns / 1e9 / t["window_s"] * 100

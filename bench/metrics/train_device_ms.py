"""Device busy time per round: the union of the device's operations in the
traced window over the rounds completed in it, in ms."""


def read(run):
    t = getattr(run, "trace", None)
    if not t or not t["n_ops"] or not run.rounds:
        return None
    return t["busy_s"] / run.rounds * 1e3

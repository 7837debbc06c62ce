"""Share of the traced serving window in which the device ran no
operation, in percent."""


def read(run):
    t = getattr(run, "trace", None)
    if not t or not t["n_ops"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100

"""The longest stretch of the serving window in which no request
completed, in ms (host clock): the process's freezes, which set the
latency tail."""


def read(run):
    probe = getattr(run, "probe", None)
    return probe["completion_gap_max_ms"] if probe else None

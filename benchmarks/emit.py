"""Benchmark records: atomic ``BENCH_*.json`` emission and the host RSS
high-water mark. Imports nothing heavier than the standard library."""
from __future__ import annotations

import json
import os
import resource
import sys

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def write_bench_json(name: str, payload: dict) -> str:
    """Atomic, unconditional ``BENCH_*.json`` emission.

    Every benchmark writes its record through here so results can't rot
    silently: the write happens even when acceptance warnings fire
    (callers must write BEFORE asserting), and it stages to a ``.tmp``
    sibling and ``os.replace``s into place so a crashed or concurrent
    run (e.g. under ``make`` with a dirty tree) can never leave a
    truncated JSON for the next comparison to misread.
    """
    import tempfile

    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    # unique tmp per writer: concurrent runs of the same bench must not
    # interleave into one staging file
    fd, tmp = tempfile.mkstemp(dir=RESULTS_DIR, prefix=name + ".", suffix=".tmp")
    try:
        # mkstemp creates 0600; restore umask-default perms so CI
        # artifact collectors and group readers keep access
        umask = os.umask(0)
        os.umask(umask)
        os.chmod(tmp, 0o666 & ~umask)
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    print(f"wrote {path}")
    return path


def max_rss_mb() -> float:
    """Host RAM high-water mark of THIS process, in MiB (getrusage;
    ru_maxrss is KiB on Linux, bytes on macOS)."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return rss / 1024 / (1024 if sys.platform == "darwin" else 1)

"""Reduction of a profiler trace to the window's events, device busy time
and idle gaps.

A trace reduces to two lists of ``(name, start_ns, end_ns)`` events: the
operations each device ran (the ``XLA Ops`` line of every ``/device:TPU:n``
plane) and the host spans the benchmark placed around its calls into the
program (``jax.profiler.TraceAnnotation``s named ``bench.*`` or after the
layer they enter). ``window`` clips both to the ``bench.window`` span; the
per-layer readers (``bench/metrics``) take their kernel, collective or
phase times from those clipped lists, and ``op_shape`` reads an op's
output shape from its label. The trace carries no op's named scope:
``op_scopes`` reads them from the compiled module's HLO text, and
``scope_of`` looks a label up there. ``summarize`` needs nothing else, so
the tests feed it synthetic events.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "FederatedBatcher.", "ServingEngine.")


def start(log_dir: str) -> None:
    """Start the profiler without the Python function tracer (host spans
    come from ``TraceAnnotation``s alone)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def op_label(module: str, hlo: str) -> str:
    """``module:instruction kind type`` from an op's HLO text, e.g.
    ``jit_fn:fusion.1 fusion f32[64,256]``."""
    m = _HLO.match(hlo)
    if not m:
        return f"{module}:{hlo[:80]}"
    return f"{module}:{m.group(1)} {m.group(4)} {m.group(2) or m.group(3)}"


_HLO = re.compile(r"^%?([\w.\-]+) = (?:\(([a-z0-9]+\[[0-9,]*\]).*?\)"
                  r"|([a-z0-9]+\[[0-9,]*\])\S*) ([\w\-]+)\(")


def op_shape(label: str) -> tuple:
    """(dtype, dims) of the output type that ends an ``op_label``, e.g.
    ``("f32", (64, 256))``; ``(None, ())`` where the label has none."""
    m = _TYPE.search(label)
    if not m:
        return None, ()
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


_TYPE = re.compile(r" ([a-z0-9]+)\[([0-9,]*)\]$")


def op_scopes(hlo_text: str, labels) -> dict:
    """``{"module:instruction": scope}`` for the op of each ``op_label`` in
    ``labels``, from a compiled module's HLO text. The scope is the
    ``jax.named_scope`` path in the instruction's ``op_name`` metadata,
    with the enclosing jits (``jit(round_fn)``), the transforms
    (``jvp(...)``, ``vmap()``) and the primitive left out: ``aggregate/score``
    for ``jit(round_fn)/aggregate/score/vmap()/dot_general``. An op with no
    metadata, no scope in it, or from another module maps to None."""
    head = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M)
    module = head.group(1) if head else "?"
    found = {f"{module}:{m.group(1)}": _scope(m.group(2))
             for m in _OP_NAME.finditer(hlo_text)}
    return {h: found.get(h) for h in {label.split(" ", 1)[0] for label in labels}}


_OP_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?"
                      r"metadata=\{[^}\n]*?op_name=\"([^\"]*)\"", re.M)


def _scope(op_name: str):
    parts, depth, cur = [], 0, ""
    for ch in op_name:  # split on the slashes outside parentheses
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    return "/".join(p for p in parts if "(" not in p) or None


def scope_of(label: str, scopes: dict):
    """The named scope ``op_scopes`` gave the op of ``label``, or None."""
    return scopes.get(label.split(" ", 1)[0])


def _device_events(plane) -> list:
    mods, ops = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0]) for e in line.events)
        elif line.name == "XLA Ops":
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
    starts = [m[0] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((op_label(mod, name), s, e))
    return out


def load(log_dir: str) -> tuple:
    """(device_ops, host_spans, n_devices) from the ``.xplane.pb`` that
    ``jax.profiler`` wrote under ``log_dir``. device_ops maps a device
    plane name to its op events, labelled by ``op_label``; host_spans is
    one list over all host threads."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    ops, spans = {}, []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                ops[plane.name] = _device_events(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIXES)]
    return ops, spans, len(ops)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def _covering(spans: list, points: list) -> list:
    """For each of the ascending ``points``: the names of the spans that
    cover it, sorted and joined with ``|`` (``(none)`` for none). One
    sweep over the span edges."""
    edges = sorted([(s, 1, n) for n, s, e in spans]
                   + [(e, 0, n) for n, s, e in spans])
    live, out, i = collections.Counter(), [], 0
    for p in points:
        while i < len(edges) and edges[i][0] <= p:
            t, opens, name = edges[i]
            live[name] += 1 if opens else -1
            i += 1
        out.append("|".join(sorted(n for n, c in live.items() if c > 0))
                   or "(none)")
    return out


def window(device_ops: dict, host_spans: list) -> tuple:
    """(ops, spans, (lo, hi)): every device's ops and the host spans other
    than the window's own, clipped to the ``bench.window`` span."""
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0]
    ops = {plane: _clip(evs, lo, hi) for plane, evs in device_ops.items()}
    spans = _clip([ev for ev in host_spans if ev[0] != WINDOW_SPAN], lo, hi)
    return ops, spans, (lo, hi)


def summarize(device_ops: dict, host_spans: list, top: int = 10) -> dict:
    """Busy, idle and per-op device time inside the ``bench.window`` span.

    Busy is the union of a device's op intervals, averaged over devices.
    Idle gaps are the holes in device 0's union; each is charged to the
    bench and program spans that cover its midpoint (joined with ``|``,
    ``(none)`` when nothing covers it).
    """
    ops, spans, (lo, hi) = window(device_ops, host_spans)
    busy_ns, per_op, n_ops = 0, collections.Counter(), 0
    first = None
    for plane in sorted(ops):
        evs = ops[plane]
        n_ops += len(evs)
        union = _union([(s, e) for _, s, e in evs])
        busy_ns += sum(e - s for s, e in union)
        for name, s, e in evs:
            per_op[name] += e - s
        if first is None:
            first = union
    n_dev = max(len(ops), 1)
    holes, prev = [], lo
    for s, e in (first or []) + [[hi, hi]]:
        if s > prev:
            holes.append(((prev + s) // 2, s - prev))
        prev = max(prev, e)
    gaps = collections.Counter()
    for who, ns in zip(_covering(spans, [m for m, _ in holes]), holes):
        gaps[who] += ns[1]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "n_ops": n_ops,
        "device_ops": [[n, t / 1e9] for n, t in per_op.most_common(top)],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps.most_common(top)],
    }


def reduce(log_dir: str) -> dict:
    """What a traced run hands the per-layer readers: the summary above
    and the window's clipped ``ops`` (by device plane) and ``spans``."""
    import time

    t = time.perf_counter()
    device_ops, host_spans, _ = load(log_dir)
    ops, spans, _ = window(device_ops, host_spans)
    return {"trace": summarize(device_ops, host_spans), "ops": ops,
            "spans": spans, "trace_read_s": time.perf_counter() - t}

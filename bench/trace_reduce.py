"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes;
``jax.profiler.ProfileData`` reads it.  Device operations are the events of
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, named
``<program>:<op>`` by the ``XLA Modules`` event they fall in.  Busy time is
the union of their intervals inside the window, averaged over the chips; an
idle gap is a stretch of the window in which no operation ran.  An op that
contains the next one (a ``while`` around its body) counts toward busy time
but not among the top ops, whose time its body's ops already hold.

The program's own spans (``repro.obs`` events, on the host's
``perf_counter`` clock) are put on the trace's clock by matching the
``fused_round`` spans to the annotations they wrote into the trace (a span
still open when the trace stopped left none), and each idle gap is named by
the innermost span open at its middle.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "fused_round"


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                       # mean over chips
    window_s: float
    devices: int
    top_ops: list[tuple[str, float]]    # (op name, seconds), all chips
    gaps: list[tuple[str, float]]       # (host span, seconds), chip 0


def find_xplane(logdir: str | Path) -> Path:
    found = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _short(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``;
    ``jit_step(1234)`` -> ``jit_step``."""
    return hlo.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def _named_ops(plane) -> list[tuple[str, float, float, bool]]:
    """(``program:op``, start, end, contains the next op), by start."""
    lines = {line.name: sorted(_events(line), key=lambda e: e[1])
             for line in plane.lines}
    modules = lines.get(MODULES_LINE, [])
    ops = lines.get(OPS_LINE, [])
    out, m = [], 0
    for i, (name, s, e) in enumerate(ops):
        while m + 1 < len(modules) and modules[m + 1][1] <= s:
            m += 1
        prog = (_short(modules[m][0]) + ":"
                if modules and modules[m][1] <= s < modules[m][2] else "")
        outer = i + 1 < len(ops) and ops[i + 1][1] < e
        out.append((prog + _short(name), s, e, outer))
    return out


def read(path: str | Path):
    """(device ops per chip, host ``ANCHOR`` annotation starts):
    ``[[(name, start_ns, end_ns, outer), ...] per chip], [start_ns, ...]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, anchors = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(_named_ops(plane))
        elif plane.name.startswith("/host:"):
            anchors += [s for line in plane.lines
                        for n, s, _ in _events(line) if n == ANCHOR]
    return devices, sorted(anchors)


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clock_offset_ns(anchors: list[int], spans: list[dict]) -> float:
    """Trace ns minus obs seconds * 1e9, from matched ``ANCHOR`` starts."""
    starts = sorted(ev["ts"] for ev in spans if ev["name"] == ANCHOR)
    pairs = list(zip(anchors, starts))
    if not pairs:
        raise ValueError(f"no {ANCHOR} annotation in the trace to align "
                         "spans")
    return statistics.median(a - s * 1e9 for a, s in pairs)


def _innermost(spans: list[dict], t: float) -> str:
    open_ = [ev for ev in spans if ev["ts"] <= t <= ev["ts"] + ev["dur"]]
    if not open_:
        return "(no span)"
    return max(open_, key=lambda ev: (ev["depth"], ev["ts"]))["name"]


def reduce(path: str | Path, spans: list[dict], window: tuple[float, float],
           top: int = 10) -> TraceSummary:
    """Summarise the trace at ``path`` over ``window`` (obs-clock seconds).
    ``spans`` are the obs span events recorded while the trace ran."""
    devices, anchors = read(path)
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PLANE.pattern} plane")
    off = clock_offset_ns(anchors, spans)
    lo, hi = window[0] * 1e9 + off, window[1] * 1e9 + off
    busy, totals = [], {}
    for ops in devices:
        inside = [(max(s, lo), min(e, hi)) for _, s, e, _ in ops
                  if e > lo and s < hi]
        busy.append(union(inside))
        for name, s, e, outer in ops:
            if e > lo and s < hi and not outer:
                totals[name] = totals.get(name, 0.0) + (min(e, hi)
                                                        - max(s, lo)) / 1e9
    edges = [lo] + [x for s, e in busy[0] for x in (s, e)] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_innermost(spans, ((s + e) / 2 - off) / 1e9), (e - s) / 1e9)
             for s, e in gaps[:top]]
    busy_s = statistics.fmean(sum(e - s for s, e in b) for b in busy) / 1e9
    ops_top = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(busy_s=busy_s, window_s=(hi - lo) / 1e9,
                        devices=len(devices), top_ops=ops_top, gaps=named)

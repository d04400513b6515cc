"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle
time, per-operation device time, and the gaps between chunk programs, each
gap attributed to the host span that covers it.

The JAX profiler writes one plane per chip (``/device:TPU:<n>``) holding a
line of operation events (``XLA Ops``) and a line of program events
(``XLA Modules``), and one host plane (``/host:CPU``) with a line per
thread.  All timestamps are on one clock.  The benchmark marks its measured
window with a host span named ``WINDOW``.

The functions below take plain lists of ``(start_ns, end_ns, name)`` so
that they can be checked on a hand-made trace (``tests/``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line):
    return [(float(e.start_ns), float(e.end_ns), e.name) for e in line.events]


def device_lines(pd, line_name: str) -> dict[int, list]:
    """``{chip: [(start_ns, end_ns, name), ...]}`` of one device line."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == line_name:
                out.setdefault(int(m.group(1)), []).extend(_events(line))
    return out


def host_spans(pd) -> list:
    """Every host event with a duration, from every thread."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend(ev for ev in _events(line) if ev[1] > ev[0])
    return out


def find_window(spans) -> tuple[float, float]:
    hits = [(s, e) for s, e, n in spans if n == WINDOW]
    if not hits:
        raise ValueError(f"no host span named {WINDOW!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)


def clip(events, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def union(events) -> list[tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.4 = f32[..] ...`` ->
    ``fusion.4``."""
    return name.split(" = ", 1)[0].lstrip("%")


def per_name_ns(events) -> dict[str, float]:
    out: dict[str, float] = defaultdict(float)
    for s, e, n in events:
        out[short(n)] += e - s
    return dict(out)


def program_gaps(modules, pattern: str) -> list[tuple[float, float]]:
    """Gaps between consecutive programs whose name contains ``pattern``:
    from the end of one to the start of the next."""
    runs = sorted((s, e) for s, e, n in modules if pattern in n)
    return [(a[1], b[0]) for a, b in zip(runs, runs[1:]) if b[0] > a[1]]


def dropped_runs(modules, ops) -> list[tuple[float, float]]:
    """Program runs with a stretch longer than 1 ms and 1% of the run in
    which none of its operations runs.  The TPU profiler keeps device
    events in buffers of a fixed size and drops what does not fit, while
    the program line, which holds few events, survives; a busy time read
    from such a trace would count the lost stretch as idle."""
    ops = sorted(ops)
    starts = [s for s, _, _ in ops]
    out = []
    for s, e, _ in modules:
        inside = ops[bisect.bisect_left(starts, s):bisect.bisect_right(
            starts, e)]
        widest = max((b - a for a, b in idle_gaps(inside, s, e)), default=0)
        if widest > max(1e6, 0.01 * (e - s)):
            out.append((s, e))
    return out


def attribute(gap, spans, exclude=(WINDOW,)) -> str:
    """The host span that explains a gap: the shortest span covering at
    least half of it (the most specific thing the host was doing then);
    failing that, the span name whose events cover most of the gap."""
    lo, hi = gap
    need = 0.5 * (hi - lo)
    best = None
    by_name: dict[str, list] = defaultdict(list)
    for s, e, n in spans:
        if n in exclude or e <= lo or s >= hi:
            continue
        by_name[n].append((s, e, n))
        cover = min(e, hi) - max(s, lo)
        if cover >= need and (best is None or e - s < best[0]):
            best = (e - s, n)
    if best:
        return best[1]
    if not by_name:
        return "(no host span)"
    return max(by_name, key=lambda n: busy_ns(by_name[n], lo, hi))


def summarize(pd, *, program: str | None = None, chips: int = 1,
              top: int = 10) -> dict:
    """Everything the per-layer metrics and the breakdown read.

    ``program`` names the chunk program (a substring of its module name)
    whose consecutive runs give ``program_gaps``.
    """
    spans = host_spans(pd)
    lo, hi = find_window(spans)
    ops = device_lines(pd, OPS_LINE)
    modules = device_lines(pd, MODULES_LINE)
    used = sorted(ops)[:chips] or sorted(modules)[:chips]
    if not used:
        raise ValueError("the trace holds no TPU device plane")
    for c in used:
        lost = dropped_runs(clip(modules.get(c, []), lo, hi),
                            clip(ops.get(c, []), lo, hi))
        if lost:
            raise ValueError(
                f"the trace of chip {c} holds no operations for part of "
                f"{len(lost)} program runs ({sum(e - s for s, e in lost) * 1e-9:.3f}"
                " s in all): the profiler dropped device events; trace a "
                "shorter window")
    busy = [busy_ns(ops.get(c) or modules.get(c, []), lo, hi) for c in used]
    first = ops.get(used[0]) or modules.get(used[0], [])
    op_ns = per_name_ns(clip(first, lo, hi))
    gaps = idle_gaps(first, lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    runs = [(s, e) for s, e, n in clip(modules.get(used[0], []), lo, hi)
            if program and program in n]
    pg = program_gaps(clip(modules.get(used[0], []), lo, hi),
                      program) if program else []
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "program_runs": len(runs),
        "program_s": sum(e - s for s, e in runs) * 1e-9,
        "program_gaps_s": [(e - s) * 1e-9 for s, e in pg],
        "program_gap_spans": [attribute(g, spans) for g in pg],
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[attribute(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
    }

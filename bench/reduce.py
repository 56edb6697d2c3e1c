"""Reduction of a profiler trace to the benchmark's device numbers.

Pure functions over plain event tuples ``(name, start_ns, duration_ns)``,
so that ``bench/tests/test_reduce.py`` checks them on a small recorded
trace without a chip.

- busy time: the union of the intervals in which an operation ran on the
  device, clipped to the traced window; idle share = 1 - busy / window;
- idle gaps: the complement of that union in the window, each named by the
  innermost host span that covers its midpoint;
- per-executable device time: the summed durations of a module's events;
- roofline share: the least time the chip could take for the work (the
  larger of FLOPs over the FLOP peak and bytes over the bandwidth peak),
  over the measured device time.
"""
from __future__ import annotations

import collections

UNNAMED = "(no host span)"


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals of ``(start, end)`` pairs, clipped
    to ``[lo, hi)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    """Union of the events' intervals inside the window, in ns."""
    return float(sum(e - s for s, e in union(
        ((t, t + d) for _, t, d in events), lo, hi)))


def idle_gaps(events, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch of the window with no event."""
    gaps, cursor = [], lo
    for s, e in union(((t, t + d) for _, t, d in events), lo, hi):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def name_gaps(gaps, host_spans, top: int = 10) -> list:
    """Idle seconds per host activity: each gap goes to the shortest host
    span ``(name, start_ns, duration_ns)`` that covers its midpoint.
    Returns the ``top`` names by idle seconds, largest first."""
    spans = sorted(host_spans, key=lambda x: x[1])
    idle: collections.Counter = collections.Counter()
    active: list = []
    nxt = 0
    for s, e in sorted(gaps):
        mid = (s + e) / 2.0
        while nxt < len(spans) and spans[nxt][1] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [x for x in active if x[1] + x[2] >= mid]
        best = min(active, key=lambda x: x[2]) if active else None
        idle[best[0] if best else UNNAMED] += (e - s) / 1e9
    return [[k, v] for k, v in idle.most_common(top)]


def time_by_name(events, top: int | None = None) -> list:
    """``[[name, seconds], ...]`` summed per event name, largest first."""
    tot: collections.Counter = collections.Counter()
    for name, _, d in events:
        tot[name] += d / 1e9
    return [[k, v] for k, v in tot.most_common(top)]


def module_seconds(events, match) -> float:
    """Device seconds of the modules whose name satisfies ``match``."""
    return sum(d for name, _, d in events if match(name)) / 1e9


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> float | None:
    """Share (%) of the roofline: least time for the work over the time
    measured; ``None`` when nothing was measured."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds

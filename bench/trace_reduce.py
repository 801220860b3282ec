"""Reduction of a profiler trace to the benchmark's device numbers.

Input is a flat list of events ``(plane, line, name, start_ns, dur_ns)``
(:func:`load_events` reads them from an ``.xplane.pb``), all on the
profiler's clock.  Device planes are ``/device:TPU:<i>``; their ``XLA Ops``
line holds one event per executed operation and ``XLA Modules`` one per
executed program.  Host spans come either from the benchmark's own
``TraceAnnotation`` events on the ``/host:CPU`` plane or from the program's
spans, mapped onto this clock by :func:`to_profiler_clock`.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]
Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def load_events(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint union of half-open intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def host_span(events: Sequence[Event], name: str) -> Optional[Interval]:
    """The first host annotation of that name, as (start, end)."""
    for plane, _, ev, t, d in events:
        if plane == HOST_PLANE and ev == name:
            return (t, t + d)
    return None


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})


def busy(events: Sequence[Event], window: Interval) -> Dict[str, object]:
    """Busy intervals per device inside the window (union of its op
    events), and the busy seconds averaged over the devices that ran."""
    per_dev = {}
    for plane in device_planes(events):
        ivs = [(t, t + d) for p, line, _, t, d in events
               if p == plane and line == OPS_LINE]
        per_dev[plane] = clip(union(ivs), *window)
    ran = [v for v in per_dev.values() if v]
    busy_ns = (sum(b - a for v in ran for a, b in v) / len(ran)) if ran else 0.0
    return {"per_device": per_dev, "busy_s": busy_ns * 1e-9,
            "window_s": (window[1] - window[0]) * 1e-9}


def top_ops(events: Sequence[Event], window: Interval,
            top: int = 10) -> List[List]:
    """Device operations by total time inside the window, averaged over
    the devices that ran, largest first."""
    tot: Dict[str, float] = {}
    planes = set()
    for p, line, name, t, d in events:
        if p.startswith(DEVICE_PREFIX) and line == OPS_LINE:
            a, b = max(t, window[0]), min(t + d, window[1])
            if b > a:
                tot[name] = tot.get(name, 0.0) + (b - a)
                planes.add(p)
    nd = max(len(planes), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9 / nd] for name, ns in ranked]


def module_times(events: Sequence[Event], window: Interval,
                 substring: str) -> List[float]:
    """Seconds of each execution of a program whose name holds
    ``substring``, on the first device plane that ran it, inside the
    window."""
    for plane in device_planes(events):
        out = [d * 1e-9 for p, line, name, t, d in events
               if p == plane and line == MODULES_LINE and substring in name
               and t >= window[0] and t + d <= window[1]]
        if out:
            return out
    return []


def idle_gaps(busy_ivs: Sequence[Interval], window: Interval,
              host: Sequence[Tuple[str, float, float]],
              top: int = 10) -> List[List]:
    """The longest stretches of the window with no device operation, each
    named by the host span that overlaps it most, longest first.  Among
    spans that cover at least half of a gap, one that holds another of
    them is passed over: an enclosing annotation covers every gap inside
    it, and the span nested in it says what the host was doing."""
    gaps, cur = [], window[0]
    for a, b in busy_ivs:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        over = [(min(b, e) - max(a, s), s, e, name) for name, s, e in host]
        over = [o for o in over if o[0] > 0]
        half = [o for o in over if 2 * o[0] >= b - a]
        inner = [o for o in half
                 if not any(o[1] <= p[1] and p[2] <= o[2] and p != o
                            for p in half)]
        pick = max(inner or over, key=lambda o: (o[0], o[1] - o[2]),
                   default=None)
        out.append([pick[3] if pick else "no host span", (b - a) * 1e-9])
    return out


def to_profiler_clock(spans: Sequence[Dict], anchor_mono: float,
                      anchor_ns: float) -> List[Tuple[str, float, float]]:
    """Program spans (monotonic ``t0``/``t1`` seconds) as (name, start,
    end) on the profiler's clock, given one instant known on both: a
    benchmark annotation that started at ``anchor_mono`` on the monotonic
    clock and at ``anchor_ns`` on the profiler's."""
    out = []
    for s in spans:
        if s.get("t1") is None:
            continue
        out.append((s["name"], anchor_ns + (s["t0"] - anchor_mono) * 1e9,
                    anchor_ns + (s["t1"] - anchor_mono) * 1e9))
    return out

"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
layer metrics and the result line need.  The first reader of any device trace
in this repository.

Two steps, so that the arithmetic can be tested without a profiler file:
``load_xplane`` turns the file into plain tuples, ``reduce_trace`` does the
arithmetic on them.

A trace has planes.  Device planes are named ``/device:TPU:<n>``; each has
lines, of which ``XLA Ops`` holds one event per executed HLO operation and
``XLA Modules`` one per executed program.  Host planes (``/host:CPU``) have
one line per thread, with ``TraceAnnotation`` spans and, where the Python
tracer is on, one event per Python call.  Times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
Line = Tuple[str, List[Event]]
Plane = Tuple[str, List[Line]]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# spans the benchmark's own loops write (jax.profiler.TraceAnnotation)
BENCH_SPAN = "bench/"


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``start_trace`` log directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_xplane(path: str) -> List[Plane]:
    """Planes, lines and events of a profiler file as plain tuples.  Needs
    only jaxlib's reader; initialises no backend."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: List[Plane] = []
    for plane in data.planes:
        lines: List[Line] = []
        for line in plane.lines:
            events = [(short_name(e.name), float(e.start_ns), float(e.duration_ns)) for e in line.events]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO text
    (``%fusion.3 = bf16[...] fusion(...)``): keep the operation's own name."""
    head = name.split(" = ", 1)[0] if " = " in name else name
    return head.lstrip("%")[:120]


def module_label(name: str) -> str:
    """``jit_step(5847039890681305928)`` -> ``jit_step(584703)``: enough of
    the fingerprint to tell two programs of one name apart."""
    m = re.match(r"^(.*)\((\d+)\)$", name)
    return f"{m.group(1)}({m.group(2)[:6]})" if m else name


def _by_module(ops: Sequence[Event], mods: Sequence[Event], label: bool) -> List[Event]:
    """The operations that lie inside a recorded program execution (a trace
    begins and ends in the middle of one, and per-execution numbers must not
    count those halves), renamed ``<program>/<operation>`` if ``label``: two
    programs both have a ``fusion.1``."""
    import bisect

    mods = sorted(mods, key=lambda ev: ev[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            out.append((f"{module_label(mods[i][0])}/{name}" if label else name, s, d))
    return out


def union_intervals(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event]) -> List[Event]:
    """Events of one line with each duration reduced by its direct children's
    (a ``while`` operation spans the operations of its body; counted whole it
    would hide them).  Returns ``(name, start, self_duration)``."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        out.append([name, s, d])
        stack.append((s + d, len(out) - 1))
    return [(n, s, max(0.0, d)) for n, s, d in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def intersect_intervals(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intervals common to two sorted lists of disjoint ``(start, end)``: one
    pass with a pointer into each."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _host_spans(planes: Sequence[Plane], min_ns: float = 5_000.0, name_by: Optional[str] = None):
    """Host events per thread line, keyed ``<plane>|<line>|<index>``, as arrays
    ``(names, starts, ends, is a bench/ span)``.  Events shorter than
    ``min_ns`` explain no gap worth naming and are dropped (a Python tracer
    writes very many); with ``name_by`` (a pattern) only events whose name
    matches it, and the benchmark's own spans, are kept."""
    import numpy as np

    rx = re.compile(name_by) if name_by else None
    out = {}
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            continue
        for i, (lname, events) in enumerate(lines):
            evs = [ev for ev in events if ev[2] >= min_ns and (rx is None or rx.search(ev[0]) or ev[0].startswith(BENCH_SPAN))]
            if evs:
                starts = np.array([ev[1] for ev in evs])
                # several threads share a line name ("python3"): the index keeps them apart
                out[f"{pname}|{lname}|{i}"] = (
                    [ev[0] for ev in evs],
                    starts,
                    starts + np.array([ev[2] for ev in evs]),
                    np.array([ev[0].startswith(BENCH_SPAN) for ev in evs]),
                )
    return out


def _name_gap(gap: Tuple[float, float], host, marked) -> str:
    """What the host was doing during an idle gap of the device.  A span of
    the benchmark's own (``bench/...``) that covers half of the gap wins;
    else, on the ``marked`` threads (those that hold an event matching ``host_thread``;
    all threads if none does), the SHORTEST event that covers at least half of the gap: the
    innermost call that explains it."""
    import numpy as np

    s, e = gap
    need = 0.5 * (e - s)
    best_bench: Tuple[float, str] = (0.0, "")
    best_inner: Tuple[float, str] = (float("inf"), "")
    for key, (names, starts, ends, is_bench) in host.items():
        ov = np.minimum(ends, e) - np.maximum(starts, s)
        ok = ov >= need
        if not ok.any():
            continue
        bench = np.flatnonzero(ok & is_bench)
        if bench.size:
            i = bench[np.argmax(ov[bench])]
            if ov[i] > best_bench[0]:
                best_bench = (float(ov[i]), names[i])
        if not marked or key in marked:
            inner = np.flatnonzero(ok & ~is_bench)
            if inner.size:
                dur = ends[inner] - starts[inner]
                j = inner[np.argmin(dur)]
                if ends[j] - starts[j] < best_inner[0]:
                    best_inner = (float(ends[j] - starts[j]), names[j])
    return best_bench[1] or best_inner[1] or "(no host event covers it)"


def reduce_trace(
    planes: Sequence[Plane],
    *,
    host_thread: Optional[str] = None,
    name_by: Optional[str] = None,
    top: int = 10,
    cpu_rehearsal: bool = False,
) -> Optional[dict]:
    """Busy and idle time, per-operation and per-program durations, and the
    longest idle gaps named by the host's activity: by the innermost event on
    the threads that hold an event matching ``host_thread``, of the events
    whose name matches ``name_by`` (all, if not given).  Returns None when the
    trace holds no device operation: the caller must then fail, not report."""
    devices = [(n, ls) for n, ls in planes if DEVICE_PLANE.match(n)]
    if cpu_rehearsal and not devices:
        # the CPU backend has no device plane: its operations run on the
        # host's XLA threads.  Good for rehearsing the plumbing, never a metric.
        xla = [(ln, evs) for n, ls in planes for ln, evs in ls if ln.startswith("tf_XLA")]
        ops = [ev for _, evs in xla for ev in evs if not ev[0].startswith("Threadpool")]
        devices = [("/device:CPU-rehearsal", [(OPS_LINE, ops)])]
    per_dev = []
    for pname, lines in devices:
        ops = [ev for lname, evs in lines if lname == OPS_LINE for ev in evs if ev[2] > 0]
        mods = [ev for lname, evs in lines if lname == MODULES_LINE for ev in evs if ev[2] > 0]
        if not ops:
            # a backend without an ops line: take every event of the plane
            ops = [ev for _, evs in lines for ev in evs if ev[2] > 0]
        if ops:
            whole = _by_module(ops, mods, label=len({m[0] for m in mods}) > 1) if mods else ops
            per_dev.append((pname, ops, mods, whole))
    if not per_dev:
        return None

    # the traced window: first to last device event over all devices
    lo = min(ev[1] for _, ops, _, _ in per_dev for ev in ops)
    hi = max(ev[1] + ev[2] for _, ops, _, _ in per_dev for ev in ops)
    window = hi - lo
    busy_each, op_time, op_count = [], {}, {}
    mod_time: Dict[str, float] = {}
    mod_count: Dict[str, int] = {}
    for _, ops, mods, whole in per_dev:
        busy = union_intervals((s, s + d) for _, s, d in ops)
        busy_each.append(_total(_clip(busy, lo, hi)))
        for name, _, d in self_times(whole):
            op_time[name] = op_time.get(name, 0.0) + d
            op_count[name] = op_count.get(name, 0) + 1
        for name, _, d in mods:
            mod_time[name] = mod_time.get(name, 0.0) + d
            mod_count[name] = mod_count.get(name, 0) + 1
    n = len(per_dev)
    # gaps are read on the first device: with one program over the mesh the
    # devices idle together, and the host is one
    _, ops0, mods0, whole0 = per_dev[0]
    busy0 = union_intervals((s, s + d) for _, s, d in ops0)
    gaps = _gaps(busy0, lo, hi)
    host = _host_spans(planes, name_by=name_by)
    rx = re.compile(host_thread) if host_thread else None
    marked = {k for k, v in host.items() if rx and any(rx.search(n) for n in v[0])}
    named: Dict[str, float] = {}
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        key = _name_gap(gap, host, marked)
        named[key] = named.get(key, 0.0) + (gap[1] - gap[0])
    ns = 1e-9
    return {
        "devices": n,
        "window_s": window * ns,
        "busy_s": sum(busy_each) / n * ns,
        "busy_by_device_s": [b * ns for b in busy_each],
        # seconds per device (events of all devices summed, divided by n)
        "op_s": {k: v / n * ns for k, v in op_time.items()},
        "op_count": {k: c / n for k, c in op_count.items()},
        "module_s": {k: v / n * ns for k, v in mod_time.items()},
        "module_count": {k: c / n for k, c in mod_count.items()},
        "gap_count": len(gaps),
        "idle_gaps": sorted(([k, v * ns] for k, v in named.items()), key=lambda kv: -kv[1])[:top],
        "device_ops": sorted(([k, v / n * ns] for k, v in op_time.items()), key=lambda kv: -kv[1])[:top],
        "first_device_ops": ops0,
        "first_device_whole_ops": whole0,
        "first_device_modules": sorted(mods0, key=lambda ev: ev[1]),
        "lo_ns": lo,
        "hi_ns": hi,
    }


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event of the line (a ``while`` or a
    ``call`` spans the operations of its body and is not work itself)."""
    out: List[Event] = []
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for i, (name, s, d) in enumerate(ordered):
        if i + 1 < len(ordered) and ordered[i + 1][1] < s + d:
            continue
        out.append((name, s, d))
    return out


def exposed_seconds(reduced: dict, is_collective) -> Tuple[float, float]:
    """(total, exposed) seconds of the collective operations on the first
    device: exposed is the part during which no other operation runs there.
    Only leaf operations inside whole program executions count."""
    ops = leaf_events(reduced["first_device_whole_ops"])
    coll = union_intervals((s, s + d) for name, s, d in ops if is_collective(name))
    other = union_intervals((s, s + d) for name, s, d in ops if not is_collective(name))
    total = _total(coll)
    # both are sorted unions, so the common part is one pass; it comes out in
    # the order the double loop over (collective, other) added its overlaps up,
    # which keeps the sum what it was to the last bit
    covered = _total(intersect_intervals(coll, other))
    return total * 1e-9, (total - covered) * 1e-9


def period_seconds(reduced: dict, pattern: str) -> Optional[float]:
    """Mean time from one execution of the program matching ``pattern`` to the
    next on the first device (None with fewer than two executions)."""
    rx = re.compile(pattern)
    starts = [s for name, s, _ in reduced["first_device_modules"] if rx.search(name)]
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1) * 1e-9


def host_events(planes: Sequence[Plane], pattern: str) -> List[Event]:
    """Host events whose name matches ``pattern``, sorted by start."""
    rx = re.compile(pattern)
    out = [ev for pname, lines in planes if not DEVICE_PLANE.match(pname) for _, evs in lines for ev in evs if rx.search(ev[0])]
    return sorted(out, key=lambda ev: ev[1])


def sum_matching(table: Dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def breakdown(reduced: dict) -> dict:
    """The ``breakdown`` of a traced result line: at most ten entries each."""
    return {
        "device_ops": [[k, v] for k, v in reduced["device_ops"][:10]],
        "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:10]],
    }

"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

What a TPU trace looks like (libtpu 0.0.34, jax 0.9): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per executed HLO instruction,
named by the instruction's full text; a ``while`` or ``conditional`` event
*contains* the events of its body) and ``Async XLA Ops``; and one
``/host:CPU`` plane with a line per host thread, on the same clock.  The
benchmark's own ``TraceAnnotation``s (``bench:*``) are on the host lines.

Only ``jax.profiler.ProfileData`` is used to read the file.  Everything
after :func:`load` works on plain tuples, so the arithmetic is tested on
hand-made events as well as on the recorded trace beside the tests.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast")
WINDOW_ANNOTATION = "bench:trace_window"

Interval = Tuple[float, float]          # (start_ns, end_ns)


class Event(NamedTuple):
    name: str
    start: float        # ns
    end: float          # ns


class Trace(NamedTuple):
    ops: Dict[int, List[Event]]         # chip -> 'XLA Ops' events
    async_ops: Dict[int, List[Event]]   # chip -> 'Async XLA Ops' events
    modules: Dict[int, List[Event]]     # chip -> 'XLA Modules' events
    host: Dict[str, List[Event]]        # host line -> events


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        tmp = tempfile.mkdtemp(prefix="xplane")
        try:
            plain = os.path.join(tmp, "t.xplane.pb")
            with gzip.open(path, "rb") as f, open(plain, "wb") as g:
                shutil.copyfileobj(f, g)
            return load(plain)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    async_ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Ops": ops, "Async XLA Ops": async_ops,
                        "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest[chip] = sorted(
                        (Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events), key=lambda e: e.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    for evs in host.values():
        evs.sort(key=lambda e: e.start)
    return Trace(ops, async_ops, modules, host)


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<type>.*?)\s"
                  r"(?P<opcode>[a-z][a-z0-9\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an 'XLA Ops' event name ('' if it is not HLO)."""
    m = _HLO.match(name)
    return m.group("opcode") if m else ""


def short_name(name: str, limit: int = 64) -> str:
    """``%copy.74 = bf16[8,32]{...} copy(...)`` -> ``copy.74_copy_bf16_8_32_``."""
    m = _HLO.match(name)
    if not m:
        text = name
    else:
        shape = re.sub(r"\{[^}]*\}", "", m.group("type"))
        text = f"{m.group('name')}_{m.group('opcode')}_{shape}"
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", text)[:limit]


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVE_OPCODES)


def is_mosaic_call(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(events: Iterable[Event], window: Optional[Interval]) -> List[Event]:
    if window is None:
        return list(events)
    lo, hi = window
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each event's duration less the part its nested events cover
    (``while`` bodies).  ``events`` sorted by start, properly nested."""
    out: List[List] = []
    stack: List[int] = []
    for ev in events:
        while stack and out[stack[-1]][2] <= ev.start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(ev.end, out[stack[-1]][2]) - ev.start
        out.append([ev.name, ev.end - ev.start, ev.end])
        stack.append(len(out) - 1)
    return [(n, max(d, 0.0)) for n, d, _ in out]


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------


def traced_window(trace: Trace) -> Optional[Interval]:
    """The host interval of the benchmark's ``bench:trace_window``."""
    for evs in trace.host.values():
        for e in evs:
            if e.name == WINDOW_ANNOTATION:
                return (e.start, e.end)
    return None


def busy(trace: Trace, window: Optional[Interval] = None) -> Dict:
    """Per chip: seconds in which an op ran, and the idle share of the
    window (1 - busy / window).  Without a window the span from the first
    to the last device event is used."""
    if window is None:
        starts = [e.start for evs in trace.ops.values() for e in evs]
        ends = [e.end for evs in trace.ops.values() for e in evs]
        if not starts:
            return {"window_s": 0.0, "busy_s": {}, "idle_share": {}}
        window = (min(starts), max(ends))
    span = window[1] - window[0]
    busy_s, idle = {}, {}
    for chip, evs in trace.ops.items():
        b = total(union((e.start, e.end) for e in clip(evs, window)))
        busy_s[chip] = b * 1e-9
        idle[chip] = 1.0 - b / span if span > 0 else 0.0
    return {"window_s": span * 1e-9, "busy_s": busy_s, "idle_share": idle}


def op_seconds(trace: Trace, chip: int, pred,
               window: Optional[Interval] = None) -> Tuple[float, int]:
    """Self seconds and count of the chip's ops whose name ``pred`` takes."""
    evs = clip(trace.ops.get(chip, []), window)
    picked = [(n, d) for n, d in self_times(evs) if pred(n)]
    return sum(d for _, d in picked) * 1e-9, len(picked)


def top_ops(trace: Trace, chip: int, n: int = 10,
            window: Optional[Interval] = None) -> List[List]:
    acc: Dict[str, float] = {}
    for name, d in self_times(clip(trace.ops.get(chip, []), window)):
        acc[name] = acc.get(name, 0.0) + d
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(k), v * 1e-9] for k, v in ranked]


def collectives(trace: Trace, chip: int,
                window: Optional[Interval] = None) -> Dict:
    """Seconds of the chip's collective ops (sync and async lines, merged)
    and the part of them during which no other op runs on that chip."""
    evs = clip(trace.ops.get(chip, []), window)
    coll = [(e.start, e.end) for e in evs if is_collective(e.name)]
    coll += [(e.start, e.end)
             for e in clip(trace.async_ops.get(chip, []), window)
             if is_collective(e.name)]
    containers = ("while", "conditional", "call")
    other = [(e.start, e.end) for e in evs
             if not is_collective(e.name)
             and opcode(e.name) not in containers]
    merged = union(coll)
    exposed = subtract(merged, union(other))
    return {"seconds": total(merged) * 1e-9,
            "exposed_seconds": total(exposed) * 1e-9,
            "count": len(coll)}


def idle_gaps(trace: Trace, chip: int, n: int = 5,
              window: Optional[Interval] = None) -> List[List]:
    """The ``n`` longest gaps between device ops, each named by what the
    host was doing: the ``bench:*`` annotation and the most specific other
    host event that cover most of the gap."""
    evs = clip(trace.ops.get(chip, []), window)
    merged = union((e.start, e.end) for e in evs)
    if window is None and merged:
        window = (merged[0][0], merged[-1][1])
    if window is None:
        return []
    gaps = sorted(subtract([window], merged), key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        bench, best, best_key = "unannotated", "", None
        for line_events in trace.host.values():
            for h in line_events:
                if h.end <= s or h.start >= e:
                    continue
                cover = (min(h.end, e) - max(h.start, s)) / (e - s)
                if h.name.startswith("bench:"):
                    if cover >= 0.5 and h.name != WINDOW_ANNOTATION:
                        bench = h.name
                    continue
                if h.name.startswith("$") or cover < 0.5:
                    continue
                key = (h.end - h.start)         # most specific: shortest
                if best_key is None or key < best_key:
                    best, best_key = h.name, key
        name = re.sub(r"[^A-Za-z0-9_.:\-]+", "_", f"{bench}_{best}")[:64]
        out.append([name, (e - s) * 1e-9])
    return out

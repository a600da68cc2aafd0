"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time (the union of the intervals in
which an operation ran), the idle share of the traced window, device time
per XLA program and per kernel, and the longest idle gaps named by what
the host was doing then (the benchmark's own ``TraceAnnotation`` spans).

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation run and the ``XLA Modules`` line one per program
run.  Host spans are events of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[int, int, str]          # (start_ns, end_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of intervals as sorted, disjoint ``(start, end)`` pairs."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Iterable[Event]) -> int:
    return sum(e - s for s, e in merge((s, e) for s, e, _ in ops))


def idle_gaps(ops: Sequence[Event], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """Gaps in ``[lo, hi)`` in which no operation ran."""
    gaps, cur = [], lo
    for s, e in merge((max(s, lo), min(e, hi)) for s, e, _ in ops
                      if e > lo and s < hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def base_name(name: str) -> str:
    """A program's name without the run id JAX appends (``jit_f(12)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(text: str) -> str:
    """An op event's name without the HLO text the TPU trace gives it
    (``%copy.65 = bf16[...] copy(...)`` -> ``%copy.65``)."""
    return text.split(" = ", 1)[0]


def per_name(events: Iterable[Event]) -> Dict[str, List[float]]:
    """``{name: [runs, seconds]}`` summed over events."""
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for s, e, n in events:
        acc = out[base_name(n)]
        acc[0] += 1
        acc[1] += (e - s) * 1e-9
    return dict(out)


def name_gaps(gaps: Sequence[Tuple[int, int]], spans: Sequence[Event],
              default: str = "host between ops") -> Dict[str, float]:
    """Seconds of idle per host span that overlaps each gap the most."""
    out: Dict[str, float] = collections.defaultdict(float)
    for gs, ge in gaps:
        best, best_ov = default, 0
        for s, e, n in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        out[best] += (ge - gs) * 1e-9
    return dict(out)


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float                  # traced window (device timeline)
    busy_s: float                    # union of op intervals, mean per chip
    programs: Dict[str, List[float]]  # program -> [runs, seconds]
    ops: Dict[str, List[float]]       # op -> [runs, seconds]
    idle_by_span: Dict[str, float]    # host span -> idle seconds in it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v[1] for k, v in self.ops.items() if rx.search(k))

    def program(self, pattern: str) -> Optional[List[float]]:
        """``[runs, seconds]`` of the programs whose name matches."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.programs.items() if rx.search(k)]
        if not hits:
            return None
        return [sum(h[0] for h in hits), sum(h[1] for h in hits)]

    def breakdown(self, n: int = 10) -> Dict[str, List[List]]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def summarize(device_ops: Dict[str, List[Event]],
              device_programs: Dict[str, List[Event]],
              host_spans: List[Event], lo: int, hi: int) -> Summary:
    """Reduce per-device op and program events and the host spans over
    the traced window ``[lo, hi)``: idle time at either end of it counts."""
    if not any(device_ops.values()):
        raise ValueError("the trace holds no device operation")
    if hi <= lo:
        raise ValueError(f"empty traced window [{lo}, {hi})")
    n = len(device_ops)
    busy = sum(busy_ns([(max(s, lo), min(e, hi), x) for s, e, x in evs
                        if e > lo and s < hi])
               for evs in device_ops.values()) / n
    idle: Dict[str, float] = collections.defaultdict(float)
    for evs in device_ops.values():
        for k, v in name_gaps(idle_gaps(evs, lo, hi), host_spans).items():
            idle[k] += v / n
    return Summary(
        devices=n, window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
        programs=per_name(ev for evs in device_programs.values()
                          for ev in evs if ev[1] > lo and ev[0] < hi),
        ops=per_name(ev for evs in device_ops.values() for ev in evs
                     if ev[1] > lo and ev[0] < hi),
        idle_by_span=dict(idle))


def summarize_window(device_ops: Dict[str, List[Event]],
                     device_programs: Dict[str, List[Event]],
                     host_spans: List[Event], window_span: str) -> Summary:
    """``summarize`` over the extent of the one host span named
    ``window_span``, which the harness opens just after the profiler
    starts and closes just before it stops."""
    marks = [(s, e) for s, e, n in host_spans if n == window_span]
    if len(marks) != 1:
        raise ValueError(f"expected one {window_span!r} span in the trace, "
                         f"found {len(marks)}")
    lo, hi = marks[0]
    rest = [ev for ev in host_spans if ev[2] != window_span]
    return summarize(device_ops, device_programs, rest, lo, hi)


def read_xplane(path: str, span_names: Iterable[str]
                ) -> Tuple[Dict[str, List[Event]], Dict[str, List[Event]],
                           List[Event]]:
    """Device op events, device program events (per device plane) and the
    host spans named in ``span_names``, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    progs: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         op_name(ev.name)) for ev in line.events]
                elif line.name == MODULES_LINE:
                    progs[plane.name] = [
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         ev.name) for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns), ev.name)
                          for ev in line.events if ev.name in wanted]
    for name in progs:
        ops.setdefault(name, [])
    return ops, progs, spans


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {paths}")
    return paths[0]

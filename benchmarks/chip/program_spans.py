"""Reduction of a profiler trace by the program's own host spans (the
serve path's ``repro.serve.spans.NAMES``).

Each idle gap of the device is attributed to the innermost program span
that covers it, and split where that span changes; idle under no program
span is kept apart, under ``OUTSIDE``.  With the count of each span this
gives the device idle per executor call; with the device's program events
it gives the device time per prefill, and the share of a program's runs
that lie inside the host span that issued them, which checks that host and
device events share a clock.  On one TPU v5e they did not: the device's
events led the host's spans by 1-2 ms (``clock_offset`` bounds it from
the trace, and ``shifted`` moves the device's events by it).

``xplane.summarize_window`` names gaps by the benchmark's own spans, each
gap whole to the span that overlaps it the most; this module leaves that
reduction, and the ``breakdown`` it feeds, as they are.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import xplane

Event = xplane.Event
OUTSIDE = "no program span"


def innermost(intervals: Sequence[Tuple[int, int]], spans: Sequence[Event]
              ) -> Dict[str, float]:
    """Seconds of ``intervals`` (disjoint) under each span name: every
    instant goes to the innermost span covering it, the one that started
    last (spans of one thread nest), and to ``OUTSIDE`` under none."""
    bounds = sorted({t for iv in intervals for t in iv}
                    | {t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    ivs = sorted(intervals)
    out: Dict[str, float] = collections.defaultdict(float)
    active: List[Event] = []
    nxt = cur = 0
    for a, b in zip(bounds, bounds[1:]):
        while cur < len(ivs) and ivs[cur][1] <= a:
            cur += 1
        if cur == len(ivs):
            break
        while nxt < len(by_start) and by_start[nxt][0] <= a:
            active.append(by_start[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > a]
        if ivs[cur][0] <= a:
            name = (max(active, key=lambda sp: (sp[0], -sp[1]))[2]
                    if active else OUTSIDE)
            out[name] += (b - a) * 1e-9
    return dict(out)


@dataclasses.dataclass
class ProgramSpans:
    idle: Dict[str, float]       # span name (or OUTSIDE) -> idle s, per chip
    counts: Dict[str, int]       # span name -> spans that start in the window

    def idle_under(self, name: str) -> float:
        """Idle seconds under span ``name`` and the spans named below it
        (``serve.decode`` takes ``serve.decode.sync``)."""
        return sum(v for k, v in self.idle.items()
                   if k == name or k.startswith(name + "."))

    def idle_ms_per(self, name: str, per: str) -> Optional[float]:
        """Idle under ``name`` over the count of span ``per`` (ms)."""
        n = self.counts.get(per, 0)
        return 1e3 * self.idle_under(name) / n if n else None


def reduce(device_ops: Dict[str, List[Event]], spans: Sequence[Event],
           lo: int, hi: int) -> ProgramSpans:
    """Idle of each device's ``[lo, hi)`` by innermost program span (mean
    over the devices) and the count of each span starting in it."""
    idle: Dict[str, float] = collections.defaultdict(float)
    for evs in device_ops.values():
        for k, v in innermost(xplane.idle_gaps(evs, lo, hi), spans).items():
            idle[k] += v / len(device_ops)
    counts = collections.Counter(n for s, _, n in spans if lo <= s < hi)
    return ProgramSpans(idle=dict(idle), counts=dict(counts))


def decode_host_idle_ms(ps: ProgramSpans) -> Optional[float]:
    """Device idle under ``serve.decode*`` per decode call (ms)."""
    return ps.idle_ms_per("serve.decode", "serve.decode")


def prefill_host_idle_ms(ps: ProgramSpans) -> Optional[float]:
    """Device idle under ``serve.prefill*`` per prefilled request (ms)."""
    return ps.idle_ms_per("serve.prefill", "serve.prefill.request")


def prefill_device_ms(summary: xplane.Summary) -> Optional[float]:
    """Device time of the prefill and page-scatter programs per prefill
    run (ms)."""
    runs = summary.program(r"^jit_prefill$")
    both = summary.program(r"^jit_(prefill|scatter_prefill_pages)$")
    return 1e3 * both[1] / runs[0] if runs else None


def clock_offset(runs: Sequence[Event], issued: Sequence[Event],
                 waited: Sequence[Event]) -> Tuple[int, int]:
    """Bounds ``(lo, hi)`` in ns on how far the device's events lead the
    host's spans on the trace's clock.  Each program run is paired, in
    order, with the host span that issued it (the run cannot start before
    it opens) and the one that waited for it (the run ends before it
    closes).  ``lo > hi`` means no one offset fits."""
    return (max(i[0] - r[0] for r, i in zip(runs, issued)),
            min(w[1] - r[1] for r, w in zip(runs, waited)))


def shifted(device_ops: Dict[str, List[Event]], ns: int
            ) -> Dict[str, List[Event]]:
    """The device's events ``ns`` later on the trace's clock."""
    return {k: [(s + ns, e + ns, n) for s, e, n in evs]
            for k, evs in device_ops.items()}


def inside(events: Sequence[Event], spans: Sequence[Event]
           ) -> Tuple[int, int]:
    """(events lying inside one of ``spans``, all events); ``spans`` must
    not overlap one another."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    hit = 0
    for s, e, _ in events:
        i = bisect.bisect_right(starts, s) - 1
        hit += i >= 0 and e <= spans[i][1]
    return hit, len(events)

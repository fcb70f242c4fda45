"""The program's own spans (``repro.utils.spans``) over the traced window.

The program keeps its spans in memory, on its own ``perf_counter_ns``
clock; the trace holds the benchmark's ``bench.run_frame`` spans on the
profiler's clock. The window's frames are the last *K*
``repro.nullhop.frame`` records, *K* being the number of
``bench.run_frame`` spans inside the trace window (the run's last frames
are the traced ones). The pairing holds when each frame's start lies the
same distance from its ``bench.run_frame`` start, to within
``MAX_SPREAD_NS``; the median distance is the shift that puts program
stamps on the trace's clock.

:func:`window` returns ``None`` where the program has no recorder (an
older checkout), where the pairing fails, or where the ring no longer
holds the window's first frame; every reader then reports nothing."""

from __future__ import annotations

import statistics
from dataclasses import dataclass

BENCH_FRAME = "bench.run_frame"
FRAME = "repro.nullhop.frame"
MAX_SPREAD_NS = 1_000_000


@dataclass
class Window:
    frames: list      # the window's repro.nullhop.frame records, in order
    records: list     # every record of those frames
    shift_ns: float   # add to a program stamp for the trace's clock
    all_records: list  # the whole snapshot, oldest first

    def named(self, name: str) -> list:
        return [r for r in self.records if r.name == name]

    def per_frame_ms(self, name: str) -> float:
        """Mean per frame of the summed durations of ``name``."""
        ns = sum(r.t1 - r.t0 for r in self.named(name))
        return ns / len(self.frames) / 1e6


def bench_frame_starts(trace: dict) -> list[float]:
    lo, hi = trace["window"]
    return sorted(s for name, s, d in trace["spans"]
                  if name == BENCH_FRAME and lo <= s and s + d <= hi)


def pair(starts: list[float], records: list) -> Window | None:
    """Match the ``bench.run_frame`` starts (trace clock) with the last
    ``len(starts)`` frame records; None where they do not pair."""
    frames = [r for r in records if r.name == FRAME]
    k = len(starts)
    if k == 0 or len(frames) < k:
        return None
    frames = frames[-k:]
    if records[0].t1 >= frames[0].t0:
        return None  # the ring dropped records of the window's first frame
    offsets = [b - f.t0 for b, f in zip(starts, frames)]
    if max(offsets) - min(offsets) >= MAX_SPREAD_NS:
        return None
    ids = {f.seq for f in frames}
    return Window(frames=frames,
                  records=[r for r in records if r.frame in ids],
                  shift_ns=statistics.median(offsets), all_records=records)


def window(run) -> Window | None:
    """The traced window's program records, read once per run."""
    if run.trace is None or run.trace.get("window") is None:
        return None
    key = "program_spans"
    if key not in run.data:
        try:
            from repro.utils import spans
        except ImportError:  # a program without the recorder
            run.data[key] = None
        else:
            run.data[key] = pair(bench_frame_starts(run.trace),
                                 spans.snapshot())
    return run.data[key]


def self_ns(parent, records: list) -> int:
    """``parent``'s duration less what its direct children on its own
    thread cover."""
    kids = sorted((max(r.t0, parent.t0), min(r.t1, parent.t1))
                  for r in records
                  if r.parent == parent.seq and r.thread == parent.thread)
    covered, end = 0, parent.t0
    for a, b in kids:
        a = max(a, end)
        if b > a:
            covered += b - a
            end = b
    return parent.t1 - parent.t0 - covered


def gbps(records: list) -> float | None:
    """Bytes over summed duration (bytes per ns = GB/s)."""
    ns = sum(r.t1 - r.t0 for r in records)
    return sum(r.nbytes for r in records) / ns if ns > 0 else None

"""The program's spans and counters over the traced window of a cell whose
frame is one ``ContinuousBatchingEngine.step``.

As :mod:`chipbench.harness.program_spans` does for frames: the window's
steps are the last *K* ``repro.serve.step`` records, *K* being the number
of the benchmark's ``bench.step`` spans inside the trace window, paired
when every step starts the same distance from its ``bench.step`` span to
within ``program_spans.MAX_SPREAD_NS``. :func:`window` returns ``None``
where the program has no such spans (an older checkout), where the pairing
fails or where the ring no longer holds the window's first step; every
reader then reports nothing."""

from __future__ import annotations

import statistics

from chipbench.harness.program_spans import MAX_SPREAD_NS, Window

BENCH_STEP = "bench.step"
STEP = "repro.serve.step"


def bench_step_starts(trace: dict) -> list[float]:
    lo, hi = trace["window"]
    return sorted(s for name, s, d in trace["spans"]
                  if name == BENCH_STEP and lo <= s and s + d <= hi)


def pair(starts: list[float], records: list) -> Window | None:
    steps = [r for r in records if r.name == STEP]
    k = len(starts)
    if k == 0 or len(steps) < k:
        return None
    steps = steps[-k:]
    if records[0].t1 >= steps[0].t0:
        return None  # the ring dropped records of the window's first step
    offsets = [b - f.t0 for b, f in zip(starts, steps)]
    if max(offsets) - min(offsets) >= MAX_SPREAD_NS:
        return None
    ids = {f.seq for f in steps}
    return Window(frames=steps,
                  records=[r for r in records if r.frame in ids],
                  shift_ns=statistics.median(offsets), all_records=records)


def window(run) -> Window | None:
    """The traced window's program records, read once per run."""
    if run.trace is None or run.trace.get("window") is None:
        return None
    key = "step_spans"
    if key not in run.data:
        try:
            from repro.utils import spans
        except ImportError:  # a program without the recorder
            run.data[key] = None
        else:
            run.data[key] = pair(bench_step_starts(run.trace),
                                 spans.snapshot())
    return run.data[key]


def counted(run, name: str) -> list[int] | None:
    """The readings of counter ``name`` filed in the window's steps; None
    where there is no window or the program keeps no counters."""
    w = window(run)
    if w is None:
        return None
    from repro.utils import spans
    counts = getattr(spans, "counts", None)
    if counts is None:
        return None
    ids = {f.seq for f in w.frames}
    return [c.value for c in counts() if c.name == name and c.frame in ids]


def per_step_ms(run, name: str) -> float | None:
    """Mean per window step of the summed durations of span ``name``;
    None where the window holds none of them."""
    w = window(run)
    if w is None or not w.named(name):
        return None
    return w.per_frame_ms(name)

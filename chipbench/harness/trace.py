"""From a profiler trace to device busy time, program time and idle gaps.

:class:`Tracer` records one window with JAX's profiler (Python tracer off,
host annotations kept) and :func:`extract` reduces the ``.xplane.pb`` to a
compact dict of plain lists:

- ``ops``: per device, ``[op, module, start_ns, dur_ns]`` from the device
  plane's "XLA Ops" line (the op's short HLO name; the module that was
  running when it started);
- ``modules``: per device, ``[module, start_ns, dur_ns]`` from "XLA
  Modules" (the jitted program's name without its hash);
- ``spans``: ``[name, start_ns, dur_ns]`` of the benchmark's own host
  annotations (names starting ``bench.``);
- ``window``: ``[lo_ns, hi_ns]`` of the ``bench.window`` span.

Device and host events share the profiler's clock. Everything below works
on that dict, so it is tested on a small extract of a recorded chip trace
(``tests/data``)."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SYNC_SPAN = "bench.sync"
SYNC_PROGRAM = "jit_chipbench_sync"


def chipbench_sync(x):
    """The marker program run inside each ``bench.sync`` span."""
    return x + 1


def _base(module: str) -> str:
    return module.split("(", 1)[0]


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            mods, raw_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = sorted(([_base(e.name), e.start_ns, e.duration_ns]
                                   for e in line.events), key=lambda m: m[1])
                elif line.name == "XLA Ops":
                    raw_ops = sorted(([_short_op(e.name), e.start_ns,
                                       e.duration_ns] for e in line.events),
                                     key=lambda o: o[1])
            if not mods and not raw_ops:
                continue
            # each op belongs to the module running when it started
            out, j = [], 0
            for name, t0, dur in raw_ops:
                while j + 1 < len(mods) and mods[j + 1][1] <= t0:
                    j += 1
                mod = (mods[j][0] if mods and mods[j][1] <= t0
                       <= mods[j][1] + mods[j][2] else "")
                out.append([name, mod, t0, dur])
            ops[plane.name] = out
            modules[plane.name] = mods
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    spans.sort(key=lambda s: s[1])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    window = ([win[-1][1], win[-1][1] + win[-1][2]] if win else None)
    syncs = [s for s in spans if s[0] == SYNC_SPAN]
    if window and len(syncs) >= 2:  # the stretch between the two markers
        window = [syncs[0][1] + syncs[0][2], syncs[-1][1]]
    data = {"ops": ops, "modules": modules, "spans": spans,
            "window": window}
    return align(data, clock_offset(data))


def clock_offset(data: dict) -> float:
    """Nanoseconds to add to device times to put them on the host spans'
    clock. Each ``bench.sync`` span [a, b] holds one run of the marker
    program [m0, m1], so the offset lies in [a - m0, b - m1]; the median
    of the intervals' midpoints over all markers (0 without markers)."""
    syncs = [s for s in data["spans"] if s[0] == SYNC_SPAN]
    mods = [m for ms in data["modules"].values() for m in ms
            if m[0] == SYNC_PROGRAM]
    mods.sort(key=lambda m: m[1])
    if not syncs or len(syncs) != len(mods):
        return 0.0
    mids = sorted(((a - m0) + (a + d - m0 - md)) / 2
                  for (_n, a, d), (_p, m0, md) in zip(syncs, mods))
    return mids[len(mids) // 2]


def align(data: dict, offset: float) -> dict:
    data["ops"] = {k: [[n, m, t + offset, d] for n, m, t, d in v]
                   for k, v in data["ops"].items()}
    data["modules"] = {k: [[n, t + offset, d] for n, t, d in v]
                       for k, v in data["modules"].items()}
    data["clock_offset_ns"] = offset
    return data


class Tracer:
    """Profile one stretch of the run into a temporary directory, reduce
    it, and delete the files. ``with tracer.window(): ...`` marks what is
    traced; the marker program runs at both ends of it, to align the
    device's clock with the host's."""

    def __init__(self):
        self.data: dict | None = None
        self._sync = None
        self._x = None

    def prepare(self) -> None:
        """Compile the marker program (part of set-up)."""
        import jax
        import jax.numpy as jnp

        self._sync = jax.jit(chipbench_sync)
        self._x = jnp.zeros((8, 128), jnp.float32)
        self._mark()

    def _mark(self) -> None:
        import jax

        # one fixed precision, so a driver's own setting cannot make the
        # marker compile again inside the window
        with jax.default_matmul_precision("highest"), \
                jax.profiler.TraceAnnotation(SYNC_SPAN):
            self._sync(self._x).block_until_ready()

    @contextlib.contextmanager
    def window(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self._mark()
                try:
                    yield
                finally:
                    self._mark()
        finally:
            jax.profiler.stop_trace()
            try:
                paths = sorted(glob.glob(os.path.join(
                    tmp, "**", "*.xplane.pb"), recursive=True))
                if paths:
                    self.data = extract(paths[-1])
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


# -- reduction ---------------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(data: dict, device: str) -> float:
    lo, hi = data["window"]
    iv = [(t, t + d) for _n, _m, t, d in data["ops"][device]]
    return sum(e - s for s, e in union(iv, lo, hi))


def window_ns(data: dict) -> float:
    lo, hi = data["window"]
    return hi - lo


def busy_s(data: dict) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    devs = list(data["ops"])
    return sum(busy_ns(data, d) for d in devs) / max(len(devs), 1) / 1e9


def idle_share(data: dict) -> float:
    return 1.0 - busy_s(data) * 1e9 / window_ns(data)


def module_events(data: dict, names) -> list[list]:
    """[module, start, dur] of the named programs inside the window, on
    every device."""
    lo, hi = data["window"]
    names = set(names)
    return [m for mods in data["modules"].values() for m in mods
            if m[0] in names and lo <= m[1] and m[1] + m[2] <= hi]


def self_times(ops: list) -> list[float]:
    """Each op's duration less that of the ops nested in it (a loop's op
    holds its body's): ops sorted by start."""
    own = [float(d) for _n, _m, _t, d in ops]
    stack: list[int] = []
    for i, (_n, _m, t, d) in enumerate(ops):
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= t:
            stack.pop()
        if stack and t + d <= ops[stack[-1]][2] + ops[stack[-1]][3]:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def top_ops(data: dict, n: int = 10) -> list[list]:
    """The device ops that took most time of their own in the window, by
    module/op, in seconds averaged over devices."""
    lo, hi = data["window"]
    tot: dict[str, float] = {}
    for ops in data["ops"].values():
        for (name, mod, t, _d), own in zip(ops, self_times(ops)):
            if lo <= t < hi:
                key = f"{mod}/{name}" if mod else name
                tot[key] = tot.get(key, 0.0) + own
    k = max(len(data["ops"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(data: dict, n: int = 10) -> list[list]:
    """The longest gaps in device 0's busy time inside the window, each
    named by the benchmark's host span that covers most of it."""
    lo, hi = data["window"]
    if not data["ops"]:
        return []
    dev = sorted(data["ops"])[0]
    iv = union([(t, t + d) for _n, _m, t, d in data["ops"][dev]], lo, hi)
    gaps, prev = [], lo
    for s, e in iv:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    spans = [(name, t, t + d) for name, t, d in data["spans"]
             if name != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "host:unannotated", 0.0
        for name, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > cover:
                best, cover = name, ov
        out.append([best, (b - a) / 1e9])
    return out


def breakdown(data: dict) -> dict:
    return {"device_ops": top_ops(data), "idle_gaps": idle_gaps(data)}


def per_span_module_ns(data: dict, span: str, modules) -> list[float]:
    """For each ``span`` host annotation in the window, in order, the
    device time of the named programs that started inside it."""
    lo, hi = data["window"]
    evs = sorted(module_events(data, modules), key=lambda m: m[1])
    out, j = [], 0
    for name, s, d in data["spans"]:
        if name != span or s < lo or s + d > hi:
            continue
        tot = 0.0
        while j < len(evs) and evs[j][1] < s:
            j += 1
        k = j
        while k < len(evs) and evs[k][1] <= s + d:
            tot += evs[k][2]
            k += 1
        out.append(tot)
    return out

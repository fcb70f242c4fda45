"""Where the device waits, named by the program's own spans.

    python3 chipbench/tools/gaps.py --workload roshambo.ring4 --seed 1 \
        --seconds 10

One traced run of the cell in this process (set-up, a window whose last
seconds are traced, the check), then the program's span records, put on
the trace's clock by ``harness/program_spans.py``, name what the host did
while the device sat idle:

- the window's 20 longest device-idle gaps, each named by the innermost
  program span that covers most of it, and the thread that span ran on;
- the share of idle time that no program span covers;
- per frame of the window, the mean time of every span name, and the self
  time of ``repro.nullhop.frame`` and ``repro.nullhop.stream``.

The last line of stdout is the same as one JSON object. Not part of a
benchmark run; the benchmark's own breakdown names gaps by its
``bench.*`` spans."""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def traced_run(root: pathlib.Path, workload: str, seed: int,
               seconds: float, *, require_chip: bool = True):
    """Run the cell once with its traced tail; returns the harness's
    :class:`Run` (trace included)."""
    from chipbench.harness import core, device, spec
    from chipbench.harness.trace import Tracer

    t0 = time.perf_counter()
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    device.setup_compile_cache(root)
    bench = spec.Benchmark(root)
    cell = bench.cell(workload)
    import jax
    devs = (device.require_tpu(cell.chips) if require_chip
            else jax.devices()[: cell.chips])
    ctx = core.Context(root=root, cell=cell, seed=seed, seconds=seconds,
                       trace=True, t_process0=t0, devices=devs,
                       tracer=Tracer())
    ctx.tracer.prepare()
    out = bench.driver(cell).run(ctx)
    if ctx.tracer.data is None or ctx.tracer.data["window"] is None:
        raise RuntimeError("the run recorded no trace window")
    return core.Run(cell=cell, seconds=seconds, setup_s=ctx.setup_s,
                    data=out.data, trace=ctx.tracer.data, peaks=None)


def idle_intervals(trace: dict) -> list[tuple[float, float]]:
    """Device 0's idle intervals inside the window (trace clock)."""
    from chipbench.harness.trace import union

    lo, hi = trace["window"]
    ops = trace["ops"][sorted(trace["ops"])[0]] if trace["ops"] else []
    out, prev = [], lo
    for s, e in union([(t, t + d) for _n, _m, t, d in ops], lo, hi):
        if s > prev:
            out.append((prev, s))
        prev = e
    if hi > prev:
        out.append((prev, hi))
    return out


def name_gap(a: float, b: float, spans: list) -> tuple[str, str, float]:
    """The innermost span covering at least half of [a, b] (else the one
    covering most of it): (name, thread, share of the gap covered)."""
    best, best_key = ("host:no program span", "", 0.0), None
    for name, thread, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        share = ov / (b - a)
        key = (share >= 0.5, -(e - s) if share >= 0.5 else share)
        if best_key is None or key > best_key:
            best, best_key = (name, thread, share), key
    return best


def covered(a: float, b: float, merged: list) -> float:
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in merged)


def report(run, n_gaps: int = 20) -> dict:
    from chipbench.harness import program_spans
    from chipbench.harness.trace import union

    w = program_spans.window(run)
    if w is None:
        raise RuntimeError("the program's spans do not pair with the trace")
    lo, hi = run.trace["window"]
    sh = w.shift_ns
    spans = [(r.name, r.thread, r.t0 + sh, r.t1 + sh) for r in w.all_records
             if r.t1 + sh > lo and r.t0 + sh < hi]
    merged = union([(s, e) for _n, _t, s, e in spans], lo, hi)
    idle = idle_intervals(run.trace)
    idle_ns = sum(b - a for a, b in idle)
    bare_ns = sum((b - a) - covered(a, b, merged) for a, b in idle)
    gaps = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:n_gaps]:
        name, thread, share = name_gap(a, b, spans)
        gaps.append({"ms": (b - a) / 1e6, "span": name, "thread": thread,
                     "covered": share})
    k = len(w.frames)
    per: dict[str, list] = collections.defaultdict(lambda: [0, 0])
    for r in w.records:
        per[r.name][0] += r.t1 - r.t0
        per[r.name][1] += 1
    per_frame = {name: {"ms": ns / k / 1e6, "count": c / k}
                 for name, (ns, c) in sorted(per.items())}
    selfs = {}
    for name in ("repro.nullhop.frame", "repro.nullhop.stream"):
        rs = w.named(name)
        if rs:
            selfs[name] = sum(program_spans.self_ns(r, w.records)
                              for r in rs) / k / 1e6
    # the same frames timed from outside: run_frame's wall less the
    # streamed layers' FrameTiming (what nullhop_host_ms reads)
    wall, streamed = run.data["wall_s"][-k:], run.data["frame_s"][-k:]
    host_ms = (sum(wall) - sum(streamed)) / k * 1e3
    return {"workload": run.cell.name, "frames": k,
            "window_ms": (hi - lo) / 1e6, "idle_ms": idle_ns / 1e6,
            "idle_uncovered_share": bare_ns / idle_ns if idle_ns else None,
            "gaps": gaps, "per_frame": per_frame, "self_ms": selfs,
            "host_ms_outside": host_ms}


def show(rep: dict) -> None:
    print(f"{rep['workload']}: {rep['frames']} frames in "
          f"{rep['window_ms']:.1f} ms traced; device idle "
          f"{rep['idle_ms']:.1f} ms, of which no program span covers "
          f"{100 * (rep['idle_uncovered_share'] or 0):.2f}%")
    print("longest idle gaps (ms, innermost span covering most, thread, "
          "covered):")
    for g in rep["gaps"]:
        print(f"  {g['ms']:9.3f}  {g['span']:34s} {g['thread']:24s} "
              f"{100 * g['covered']:5.1f}%")
    print("per frame (mean ms, spans a frame):")
    for name, v in rep["per_frame"].items():
        print(f"  {name:34s} {v['ms']:9.3f} {v['count']:7.2f}")
    for name, ms in rep["self_ms"].items():
        print(f"  self {name:29s} {ms:9.3f}")
    print(f"  {'run_frame wall - FrameTiming.frame_s':39s} "
          f"{rep['host_ms_outside']:9.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/tools/gaps.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rep = report(traced_run(ROOT, args.workload, args.seed, args.seconds))
    show(rep)
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

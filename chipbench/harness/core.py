"""One run of one cell: set-up, the measured window, the check, the line.

The driver named by the cell's configuration does the work through a
:class:`Context`; this module owns what every cell shares: the chip check,
the compile cache, set-up time, the traced stretch, the metric readers,
and the result line (last line of stdout) with the numbers compared
(last lines of stderr)."""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import pathlib
import sys
import time
from dataclasses import dataclass, field
from typing import Any

from chipbench.harness import spec as spec_mod
from chipbench.harness.trace import Tracer

# JAX records this for every program it compiles or loads from its cache
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


@dataclass(frozen=True)
class Check:
    """One number compared with its limit: correct while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    attempted: int
    failed: int
    checks: list[Check]
    data: dict[str, Any]            # what the metric readers read
    device: dict[str, Any]          # describe() taken before the reference
    notes: list[str] = field(default_factory=list)


@dataclass
class Context:
    root: pathlib.Path
    cell: spec_mod.Cell
    seed: int
    seconds: float
    trace: bool
    t_process0: float
    devices: list = field(default_factory=list)
    control: bool = False  # the control in the program's place, in the check
    setup_s: float | None = None
    tracer: Tracer | None = None
    tracing: bool = False
    compiles: list = field(default_factory=lambda: [0])  # programs compiled
    window_compiles: int | None = None  # or loaded, after set-up

    def count_compile(self, event: str, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles[0] += 1

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_process0
        self._compiles_at_setup = self.compiles[0]

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op untraced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def trace_tail(self, stack: contextlib.ExitStack, elapsed: float,
                   tail_s: float) -> None:
        """Start tracing once ``elapsed`` reaches the window's last
        ``tail_s`` seconds (once; ``stack`` stops it)."""
        if self.trace and not self.tracing \
                and elapsed >= self.seconds - tail_s:
            self.tracing = True
            stack.enter_context(self.tracer.window())

    def read_device(self) -> dict:
        """The device's description and peak memory, read as the window
        (and its drain) ends."""
        from chipbench.harness.device import describe
        if self.setup_s is not None:
            self.window_compiles = self.compiles[0] - self._compiles_at_setup
        return describe(self.devices)


@dataclass
class Run:
    """What a metric reader sees."""

    cell: spec_mod.Cell
    seconds: float
    setup_s: float
    data: dict
    trace: dict | None
    peaks: dict | None


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="check the control (the reference one precision "
                    "step lower) in the program's place: a test of the "
                    "check, which has to come out not correct")
    return ap.parse_args(argv)


def execute(root: pathlib.Path, args: argparse.Namespace, t_process0: float,
            *, require_chip: bool = True) -> tuple[dict, list[Check]]:
    """Run the cell; return the result object and the checks.

    ``require_chip=False`` is for the harness's own tests, which drive a
    whole run on the CPU at a tiny size: no device metric is then named
    for a chip."""
    import jax

    from chipbench.harness import device as dev_mod
    from chipbench.harness.peaks import peaks_for
    from chipbench.harness.trace import breakdown, busy_s, window_ns

    bench = spec_mod.Benchmark(root)
    cell = bench.cell(args.workload)
    if args.seed < 0:
        raise ValueError("--seed must be non-negative")
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    dev_mod.setup_compile_cache(root)
    devices = (dev_mod.require_tpu(cell.chips) if require_chip
               else jax.devices()[: cell.chips])
    peaks = None
    if args.trace and (require_chip or devices[0].platform == "tpu"):
        peaks = peaks_for(devices[0].device_kind)
    driver = bench.driver(cell)
    ctx = Context(root=root, cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_process0=t_process0,
                  devices=devices, control=bool(args.control),
                  tracer=Tracer() if args.trace else None)
    if ctx.tracer is not None:
        ctx.tracer.prepare()
    jax.monitoring.register_event_listener(ctx.count_compile)
    try:
        out: Outcome = driver.run(ctx)
    finally:
        jax.monitoring.unregister_event_listener(ctx.count_compile)
    if ctx.setup_s is None:
        raise RuntimeError("the driver never marked the end of set-up")
    tdata = ctx.tracer.data if ctx.tracer else None
    if args.trace and (tdata is None or tdata["window"] is None):
        raise RuntimeError("the traced run recorded no trace window")
    run = Run(cell=cell, seconds=args.seconds, setup_s=ctx.setup_s,
              data=out.data, trace=tdata, peaks=peaks)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = bench.reader(m.name)(run)
        if value is None:
            if m.kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m.name} read nothing")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}
    device = dict(out.device)
    result: dict[str, Any] = {
        "correct": bool(out.checks) and all(c.ok for c in out.checks)
        and out.failed == 0,
        "attempted": int(out.attempted), "failed": int(out.failed),
        "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = busy_s(tdata)
        device["window_s"] = window_ns(tdata) / 1e9
        result["breakdown"] = breakdown(tdata)
    print(f"note: setup_s {ctx.setup_s:.3f}; programs compiled or loaded "
          f"after set-up, before the check: {ctx.window_compiles}",
          file=sys.stderr)
    for note in out.notes:
        print(f"note: {note}", file=sys.stderr)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result, out.checks


def main(argv=None, *, root: pathlib.Path | None = None,
         t_process0: float | None = None, require_chip: bool = True) -> int:
    t0 = time.perf_counter() if t_process0 is None else t_process0
    args = parse(argv)
    root = pathlib.Path(root) if root else pathlib.Path(__file__).resolve(
        ).parents[2]
    try:
        result, checks = execute(root, args, t0, require_chip=require_chip)
    except Exception as e:  # the run's boundary: report, print no result
        import traceback
        traceback.print_exc()
        print(f"chipbench: no result ({type(e).__name__}: {e})",
              file=sys.stderr)
        return 1
    sys.stdout.flush()
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

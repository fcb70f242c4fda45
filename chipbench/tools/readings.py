"""The two readings a cell's limit is set from, on the chip.

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--control]

For each seed, one process runs the cell as ``run.py`` does (set-up, a
window at the cell's own load, the check) and prints the number compared.
With ``--control`` the check puts the control (the reference one
precision step lower: three bf16 passes for the f32 CNN, float8 for the
bf16 LM) in the program's place, as ``run.py --control 1`` does, and
prints both readings and whether the run came out correct. Not part of a
benchmark run."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    from chipbench.harness import core, device, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    device.setup_compile_cache(ROOT)
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    devs = device.require_tpu(cell.chips)
    driver = bench.driver(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = core.Context(root=ROOT, cell=cell, seed=seed,
                           seconds=args.seconds, trace=False,
                           t_process0=t0, devices=devs,
                           control=args.control)
        out = driver.run(ctx)
        row = {"workload": cell.name, "seed": seed,
               "attempted": out.attempted, "failed": out.failed,
               **out.data["readings"],
               "correct": all(c.ok for c in out.checks) and not out.failed,
               "wall_s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Find the highest arrival rate a serving cell sustains, on the chip.

    python3 chipbench/tools/knee.py --workload <cell> --rates 3,4,5,6 \
        --seconds 30 --seed 1

One process builds the cell's engine once, warms every prompt length the
mix can draw, then offers each rate for ``--seconds`` with the cell's own
size distributions and drains. A rate is sustained when at least
``ATTAINMENT`` of its requests were served within both limits, as DistServe
(arXiv:2401.09670) defines a system's goodput: the first token within
``TTFT_LIMIT_S`` of the request's due time, and the request's later tokens
at ``TPOT_LIMIT_S`` or less each on average. The knee is the highest
sustained rate. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

ATTAINMENT = 0.9
TTFT_LIMIT_S = 1.0   # an interactive user's wait for the first token
TPOT_LIMIT_S = 0.15  # about 7 tokens a second, faster than reading pace


def met_limits(f) -> bool:
    """Whether one request was served within both limits."""
    if not f.req.done or not f.times:
        return False
    tpot = ((f.times[-1] - f.times[0]) / (len(f.times) - 1)
            if len(f.times) > 1 else 0.0)
    return f.times[0] - f.due <= TTFT_LIMIT_S and tpot <= TPOT_LIMIT_S


def main(argv=None) -> int:
    import numpy as np

    from chipbench.harness import core, device, spec, traffic
    from chipbench.harness.stats import percentile

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    device.setup_compile_cache(ROOT)
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    devs = device.require_tpu(cell.chips)
    drv = bench.driver(cell)
    from repro.serve.continuous import Request

    c, tr = cell.config, cell.traffic
    pt = tr["prompt_tokens"]
    step = pt.get("round_to", 1)
    lengths = list(range(-(-pt["min"] // step) * step, pt["max"] + 1, step))
    eng = drv._engine(c, drv.lm_weights(c, args.seed))
    drv.warm(eng, lengths, c["vocab_size"])
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(tr, arrivals=dict(tr["arrivals"], rate_per_s=rate))
        sched = traffic.requests(t, args.seconds, args.seed, c["vocab_size"])
        flights = [drv._Flight(s, Request(rid=s.rid, prompt=s.prompt,
                                          max_new_tokens=s.max_new_tokens),
                               s.due_s) for s in sched]
        ctx = core.Context(root=ROOT, cell=cell, seed=args.seed,
                           seconds=args.seconds, trace=False,
                           t_process0=time.perf_counter(), devices=devs)
        backlog = {}

        def at_close(flights=flights, backlog=backlog):
            backlog["n"] = sum(1 for f in flights if not f.req.tokens)

        rec = drv._drive(ctx, eng, flights, t, on_close=at_close)
        done = [f for f in flights if f.req.done]
        ttft = [f.times[0] - f.due for f in done]
        itl = [g for f in done for g in np.diff(f.times)]
        dec = [w for _t, w, a, _c in rec["steps"] if a == 0]
        row = {"rate_per_s": rate, "requests": len(flights),
               "served": len(done), "backlog_at_close": backlog.get("n"),
               "drain_s": rec["drain_s"],
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p90_ms": percentile(ttft, 90) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "itl_p95_ms": percentile(itl, 95) * 1e3,
               "decode_step_ms": float(np.mean(dec)) * 1e3 if dec else None,
               "sheds": rec["sheds"],
               "attainment": sum(map(met_limits, flights)) / len(flights)}
        row["sustained"] = row["attainment"] >= ATTAINMENT
        print(json.dumps(row), flush=True)
    eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

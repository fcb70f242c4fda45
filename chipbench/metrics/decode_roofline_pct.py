"""Roofline time of the decode program over its device time in the trace:
per traced step, the larger of its operations (active slots) at the bf16
peak and its bytes (every weight, each active slot's valid cache, new
keys and values, logits) at HBM bandwidth, matched to the decode program's
device time inside that step. Memory bound at these sizes."""

from chipbench.harness import counts, records
from chipbench.harness.peaks import roofline_s
from chipbench.harness.trace import per_span_module_ns


def read(run):
    steps = records.steps(run)
    if run.trace is None or not steps:
        return None
    dev = per_span_module_ns(run.trace, "bench.step", records.DECODE_PROGRAMS)
    if not dev:
        return None
    lm = counts.DenseLM.from_config(run.data["config"])
    floor = busy = 0.0
    for (_t, _w, _a, cached), ns in zip(steps[-len(dev):], dev):
        if ns <= 0 or not cached:
            continue
        floor += roofline_s(*lm.decode_step(cached), run.peaks)[0]
        busy += ns / 1e9
    return 100.0 * floor / busy if busy else None

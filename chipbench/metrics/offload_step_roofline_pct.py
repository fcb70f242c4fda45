"""Roofline time of the decode step's device programs over their device
time in the trace: per traced step, the larger of its operations at the
bf16 peak and its bytes (resident weights, the experts present, each
row's valid latent cache, new entries, logits) at HBM bandwidth, matched
to the device time of the model's programs (jit_moonlight_*) started
inside that step. Memory bound at these sizes."""

from chipbench.harness.moonlight_counts import MoonlightLM
from chipbench.harness.peaks import roofline_s
from chipbench.harness.trace import per_span_module_ns

PROGRAMS = ("jit_moonlight_embed", "jit_moonlight_dense_layer",
            "jit_moonlight_route", "jit_moonlight_shared",
            "jit_moonlight_expert", "jit_moonlight_head")


def read(run):
    steps = run.data.get("offload_steps")
    if run.trace is None or run.peaks is None or not steps:
        return None
    dev = per_span_module_ns(run.trace, "bench.step", PROGRAMS)
    if not dev:
        return None
    lm = MoonlightLM.from_config(run.data["config"])
    floor = busy = 0.0
    for (_t, _w, cached, experts), ns in zip(steps[-len(dev):], dev):
        if ns <= 0:
            continue
        floor += roofline_s(*lm.decode_step(cached, experts), run.peaks)[0]
        busy += ns / 1e9
    return 100.0 * floor / busy if busy else None

"""Mean of the program's repro.moe.experts_moved counter over the traced
window's steps: distinct routed experts moved to the device per MoE layer
per step."""

from chipbench.harness import step_spans
from chipbench.harness.stats import mean


def read(run):
    return mean(step_spans.counted(run, "repro.moe.experts_moved") or ())

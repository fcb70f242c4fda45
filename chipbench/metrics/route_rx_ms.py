"""Mean per decode step of the summed repro.moe.route spans: per MoE layer,
from the dispatch of attention and the router until the host holds the
routed expert ids."""

from chipbench.harness import step_spans


def read(run):
    return step_spans.per_step_ms(run, "repro.moe.route")

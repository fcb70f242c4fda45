"""Mean per decode step of the summed repro.stream.expert_wait spans: the
host's waits, on the step's critical path, for routed experts to land."""

from chipbench.harness import step_spans


def read(run):
    return step_spans.per_step_ms(run, "repro.stream.expert_wait")

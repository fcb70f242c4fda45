"""75th percentile of time to first token over every request, from the
request's due time to the step that returned its first token: at the
serving cells' rates the highest percentile with ten requests beyond it
in every run."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile(run.data.get("ttft_s", ()), 75)
    return v * 1e3 if v is not None else None

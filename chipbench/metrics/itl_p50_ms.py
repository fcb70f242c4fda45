"""Median over every gap between consecutive tokens of every request, as
the host saw them returned: the pace users see on most tokens."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile(run.data.get("itl_s", ()), 50)
    return v * 1e3 if v is not None else None

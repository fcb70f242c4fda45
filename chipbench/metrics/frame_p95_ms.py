"""95th percentile of run_frame's wall time (frame in to logits out), over
every frame of the window."""

from chipbench.harness import records
from chipbench.harness.stats import percentile


def read(run):
    f = records.frames(run)
    return percentile(f["wall_s"], 95) * 1e3 if f else None

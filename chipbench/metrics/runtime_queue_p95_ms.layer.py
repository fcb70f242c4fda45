"""95th percentile of the program's repro.runtime.queue.layer spans
(submit to first dispatch of a LAYER-class descriptor) of the traced
window's frames."""

import numpy as np

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    q = w.named("repro.runtime.queue.layer") if w else []
    if not q:
        return None
    return float(np.percentile([r.t1 - r.t0 for r in q], 95)) / 1e6

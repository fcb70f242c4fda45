"""Mean per frame of run_frame's wall time less the streamed layers'
FrameTiming.frame_s: the sparsity count over the returned fmaps, the
host FC head and the executor's bookkeeping."""

import numpy as np

from chipbench.harness import records


def read(run):
    f = records.frames(run)
    if not f:
        return None
    return float(np.mean(np.subtract(f["wall_s"], f["frame_s"]))) * 1e3

"""Mean per frame of the summed LayerTiming.tx_s: time the layer loop
waited for parameters and input to reach the device (on the overlapped
path a wait on the critical path, not a transfer time)."""

import numpy as np

from chipbench.harness import records


def read(run):
    f = records.frames(run)
    return float(np.mean(f["tx_wait_s"])) * 1e3 if f else None

"""Mean per frame of the summed LayerTiming.rx_s: time the layer loop
waited for feature maps to come back to the host."""

import numpy as np

from chipbench.harness import records


def read(run):
    f = records.frames(run)
    return float(np.mean(f["rx_wait_s"])) * 1e3 if f else None

"""Mean per frame of the program's repro.nullhop.frame time that no child
span on its thread covers (layer list, executor construction, result),
over the traced window's frames."""

import numpy as np

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    if not w:
        return None
    return float(np.mean([program_spans.self_ns(f, w.records)
                          for f in w.frames])) / 1e6

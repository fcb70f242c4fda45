"""Mean per frame of the program's repro.nullhop.stream time that no layer
span on its thread covers (the submit path outside the pack spans, layout
lookups, ticket bookkeeping), over the traced window's frames."""

import numpy as np

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    if not w:
        return None
    streams = w.named("repro.nullhop.stream")
    if not streams:
        return None
    return float(np.sum([program_spans.self_ns(s, w.records)
                         for s in streams])) / len(w.frames) / 1e6

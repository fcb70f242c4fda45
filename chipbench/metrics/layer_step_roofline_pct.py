"""Roofline time of the streamed conv layers over their programs' device
time in the trace. Each layer's bound is the larger of its operations at
the bf16 peak and its bytes (fmaps in and out, weights) at HBM bandwidth;
at batch 1 every layer is bound by memory."""

from chipbench.harness import counts, records
from chipbench.harness.peaks import roofline_s
from chipbench.harness.trace import module_events


def read(run):
    if run.trace is None or records.frames(run) is None:
        return None
    layers = counts.cnn_layers(run.data["config"])
    evs = module_events(run.trace, records.LAYER_PROGRAMS)
    n_frames = len(evs) // len(layers)
    if n_frames == 0:
        return None
    device_ns = sum(d for _n, _s, d in evs[: n_frames * len(layers)])
    floor = n_frames * sum(roofline_s(l.flops, l.nbytes, run.peaks)[0]
                           for l in layers)
    return 100.0 * floor / (device_ns / 1e9)

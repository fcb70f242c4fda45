"""Mean per frame of the program's repro.nullhop.oracle span over the
traced window's frames: the per-layer sparsity, a zero count on the host
over each layer's fmap that the stream already returned."""

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return w.per_frame_ms("repro.nullhop.oracle") if w else None

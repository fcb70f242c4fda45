"""Mean per frame of the summed repro.stream.pack spans (staging pack and
submit of each layer's parameters) over the traced window's frames."""

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return w.per_frame_ms("repro.stream.pack") if w else None

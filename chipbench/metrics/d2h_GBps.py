"""Device-to-host bandwidth of the traced window's frames: summed bytes
over summed duration of the program's repro.xfer.rx spans (first chunk
start to last chunk done, per transfer)."""

from chipbench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return program_spans.gbps(w.named("repro.xfer.rx")) if w else None

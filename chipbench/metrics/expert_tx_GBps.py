"""Host-to-device bandwidth of the routed experts in the traced window's
steps: their bytes over the summed duration of their transfers (the
repro.xfer.tx records filed under each repro.stream.expert_tx span, first
segment start to last segment done)."""

from chipbench.harness import program_spans, step_spans


def read(run):
    w = step_spans.window(run)
    if w is None:
        return None
    tx = {r.seq for r in w.named("repro.stream.expert_tx")}
    return program_spans.gbps([r for r in w.named("repro.xfer.tx")
                               if r.parent in tx])

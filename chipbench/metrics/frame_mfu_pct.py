"""Model operations per frame (every conv and the FC head) times
frames_per_s, over the chip's bf16 peak."""

from chipbench.harness import counts, records


def read(run):
    f = records.frames(run)
    if not f or run.peaks is None:
        return None
    rate = len(f["wall_s"]) / f["window_s"]
    flops = counts.cnn_frame_flops(run.data["config"])
    return 100.0 * flops * rate / run.peaks["bf16_flops_per_s"]

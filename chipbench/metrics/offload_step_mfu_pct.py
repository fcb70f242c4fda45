"""Model operations of the window's decode steps (every row at its cache
length) over the window's wall time, over the chip's bf16 peak: a step's
model FLOPs times frames_per_s, over the peak."""

from chipbench.harness.moonlight_counts import MoonlightLM


def read(run):
    steps = run.data.get("offload_steps")
    if not steps or run.peaks is None:
        return None
    lm = MoonlightLM.from_config(run.data["config"])
    flops = sum(lm.step_flops(cached) for _t, _w, cached, _e in steps)
    return 100.0 * flops / (run.data["window_s"]
                            * run.peaks["bf16_flops_per_s"])

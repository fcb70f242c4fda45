"""Model operations of the decode-only steps at their active slots, over
their host wall time times the chip's bf16 peak."""

from chipbench.harness import counts, records


def read(run):
    steps = [s for s in records.steps(run) or () if s[2] == 0 and s[3]]
    if not steps or run.peaks is None:
        return None
    lm = counts.DenseLM.from_config(run.data["config"])
    flops = sum(lm.decode_step(cached)[0] for _t, _w, _a, cached in steps)
    wall = sum(w for _t, w, _a, _c in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])

"""Mean host wall time of the engine's step() calls that admitted nothing
(decode only), over every such step begun in the window."""

from chipbench.harness import records
from chipbench.harness.stats import mean


def read(run):
    s = records.steps(run)
    v = mean(w for _t, w, adm, _c in s or () if adm == 0)
    return v * 1e3 if v is not None else None

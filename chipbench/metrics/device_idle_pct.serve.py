"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), averaged over chips."""

from chipbench.harness.trace import idle_share


def read(run):
    return 100.0 * idle_share(run.trace) if run.trace else None

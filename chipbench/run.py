"""Run one cell of BENCHMARK.json once, on the chip this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up (weights and inputs from the seed, every shape warmed), measures
for ``--seconds``, checks what the timed path produced against a plain
reference, and prints one JSON object as the last line of stdout. Exits
non-zero with no result where JAX finds no TPU or fewer chips than the
cell asks for; there is no CPU fallback. ``--trace 1`` also profiles the
window's last seconds and reports the per-layer metrics."""

import pathlib
import sys
import time

T_PROCESS0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from chipbench.harness.core import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], root=ROOT, t_process0=T_PROCESS0))

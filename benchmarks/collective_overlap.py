"""Blocks-mode collective benchmark: compare the HLO of a monolithic
all-gather+matmul against the chunked ppermute ring (overlapped_matmul_ag)
— per-step collective bytes, op counts, and the overlap structure. Runs in
a subprocess with 8 fake devices (compile-only analysis, like the dry-run)."""

from __future__ import annotations

import os
import subprocess
import sys

_CODE = r"""
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import pipeline_collectives as pc
from repro.launch.hlo_cost import analyze

mesh = jax.make_mesh((8,), ("m",), axis_types=(jax.sharding.AxisType.Auto,))
M, K, N = 1024, 2048, 2048
x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16)
w = jax.ShapeDtypeStruct((K, N // 8), jnp.bfloat16)

def unique_mode(a, b):
    ag = jax.lax.all_gather(a, "m", axis=0, tiled=True)
    return ag @ b

def blocks_mode(a, b):
    return pc.overlapped_matmul_ag(a, b, "m")

out = {}
for name, fn in [("unique", unique_mode), ("blocks", blocks_mode)]:
    g = shard_map(fn, mesh=mesh, in_specs=(P("m", None), P(None, None)),
                  out_specs=P("m", None))
    c = jax.jit(g).lower(x, w).compile()
    cost = analyze(c.as_text(), 8)
    hlo = c.as_text()
    out[name] = {
        "collective_bytes": cost.collective_bytes,
        "by_kind": cost.collective_by_kind,
        "flops": cost.flops,
        "n_allgather": hlo.count(" all-gather("),
        "n_ppermute": hlo.count(" collective-permute("),
    }
print(json.dumps(out))
"""


def run() -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    # the child compiles on 8 fake host devices and must never reach for
    # the chip the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CODE], env=env,
                          capture_output=True, text=True,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))), timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"collective_overlap child exited "
                           f"{proc.returncode}: {proc.stderr[-2000:]}")
    import json
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for mode, d in data.items():
        rows.append({
            "bench": "collective_overlap", "mode": mode,
            "collective_bytes_per_dev": d["collective_bytes"],
            "flops_per_dev": d["flops"],
            "n_allgather": d["n_allgather"],
            "n_ppermute": d["n_ppermute"],
        })
    # derived: blocks mode exposes per-chunk overlap (n_ppermute steps whose
    # comm hides under the chunk dot) at equal total bytes
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)

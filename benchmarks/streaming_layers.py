"""Per-layer weight-streaming benchmark (the NullHop execution model on an
LM): serve one decode step while layer k+1's params stream host->device
under each policy. Measures the overlap gain of the cached-layout descriptor
ring (``staged-ring``) against the seed per-frame pack path (``seed-pack``)
— the paper's central claim at LM scale. Emits the old-vs-new comparison to
``BENCH_transfer.json`` so the perf trajectory is tracked across PRs."""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.streaming import HostStreamingExecutor
from repro.core.transfer import (
    Buffering,
    Management,
    Partitioning,
    TransferEngine,
    TransferPolicy,
)

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_transfer.json"


def mlp_layers(n_layers: int, d: int, f: int, key):
    """n_layers gated-MLP blocks as (name, host_params, apply)."""
    layers = []

    def apply_fn(params, x):
        wi, wo = params
        h = x @ wi
        gate, up = jnp.split(h, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo

    jitted = jax.jit(apply_fn)
    for i in range(n_layers):
        k1, k2, key = jax.random.split(key, 3)
        wi = np.asarray(jax.random.normal(k1, (d, 2 * f)) * 0.02,
                        np.float32)
        wo = np.asarray(jax.random.normal(k2, (f, d)) * 0.02, np.float32)
        layers.append((f"mlp{i}", [wi, wo], jitted))
    return layers


# (row name, path, policy, staged) — seed-pack rows run the per-frame
# np.concatenate path the repo shipped with; staged-ring rows run the
# cached-StagedLayout descriptor-ring path.
def _variants():
    return [
        ("polling-unique", "seed-pack",
         TransferPolicy.user_level_polling(), False),
        ("interrupt-single", "seed-pack",
         TransferPolicy.kernel_level(), False),
        ("interrupt-double-prefetch", "seed-pack", TransferPolicy(
            Management.INTERRUPT, Buffering.DOUBLE, Partitioning.UNIQUE),
         False),
        ("interrupt-double-staged", "staged-ring", TransferPolicy(
            Management.INTERRUPT, Buffering.DOUBLE, Partitioning.UNIQUE),
         True),
        ("interrupt-ring4-staged", "staged-ring", TransferPolicy(
            Management.INTERRUPT, Buffering.RING, Partitioning.UNIQUE,
            ring_depth=4), True),
    ]


def run(n_layers: int = 8, d: int = 1024, f: int = 4096,
        repeats: int = 3) -> list[dict]:
    key = jax.random.PRNGKey(0)
    layers = mlp_layers(n_layers, d, f, key)
    x = np.asarray(jax.random.normal(key, (8, d)), np.float32)
    rows = []
    for name, path, policy, staged in _variants():
        engine = TransferEngine(policy)
        ex = HostStreamingExecutor(engine, staged=staged)
        ex.run(layers, x)  # warmup
        best = None
        for _ in range(repeats):
            _, timing = ex.run(layers, x)
            if best is None or timing.frame_s < best.frame_s:
                best = timing
        tx = sum(l.tx_s for l in best.layers)
        rx = sum(l.rx_s for l in best.layers)
        comp = sum(l.compute_s for l in best.layers)
        rows.append({
            "bench": "streaming_layers", "policy": name, "path": path,
            "frame_ms": round(best.frame_s * 1e3, 3),
            "frames_per_s": round(1.0 / max(best.frame_s, 1e-9), 2),
            "tx_ms": round(tx * 1e3, 3),
            "rx_ms": round(rx * 1e3, 3),
            "compute_ms": round(comp * 1e3, 3),
            "tx_us_per_byte": round(best.tx_us_per_byte, 6),
            "tx_hidden_frac": round(max(0.0, 1 - tx / max(best.frame_s
                                                          - comp, 1e-9))
                                    if best.frame_s > comp else 1.0, 3),
            "bytes_per_layer": best.layers[1].tx_bytes,
        })
        engine.close()
    return rows


def write_bench_json(rows: list[dict] | None = None,
                     path: pathlib.Path | str = BENCH_JSON) -> dict:
    """Write the old-vs-new transfer comparison to BENCH_transfer.json,
    preserving sections other benchmarks merged in (e.g. ``multichannel``
    from benchmarks/multichannel_sweep.py)."""
    rows = rows if rows is not None else run()
    seed = min((r for r in rows if r["path"] == "seed-pack"
                and r["policy"].startswith("interrupt")),
               key=lambda r: r["frame_ms"])
    ring = min((r for r in rows if r["path"] == "staged-ring"),
               key=lambda r: r["frame_ms"])
    path = pathlib.Path(path)
    doc = json.loads(path.read_text()) if path.exists() else {}
    doc.update({
        "bench": "streaming_layers",
        "payload_bytes_per_layer": ring["bytes_per_layer"],
        "rows": rows,
        "seed_pack_best": seed,
        "staged_ring_best": ring,
        "tx_us_per_byte_ratio_seed_over_ring": round(
            seed["tx_us_per_byte"] / max(ring["tx_us_per_byte"], 1e-12), 3),
        "frames_per_s_ratio_ring_over_seed": round(
            ring["frames_per_s"] / max(seed["frames_per_s"], 1e-12), 3),
    })
    path.write_text(json.dumps(doc, indent=2) + "\n")
    return doc


if __name__ == "__main__":
    bench_rows = run()
    for r in bench_rows:
        print(r)
    doc = write_bench_json(bench_rows)
    print(f"wrote {BENCH_JSON}: ring/seed frames_per_s ratio "
          f"{doc['frames_per_s_ratio_ring_over_seed']}")

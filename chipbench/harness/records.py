"""What the drivers record, read the same way by every metric reader."""

from __future__ import annotations

# jitted programs of the timed paths, by the names JAX gives them
LAYER_PROGRAMS = ("jit_apply_fn",)    # NullHopExecutor's per-layer steps
DECODE_PROGRAMS = ("jit_dec",)        # ContinuousBatchingEngine's decode


def frames(run) -> dict | None:
    d = run.data
    return d if "frame_s" in d and d["wall_s"] else None


def steps(run) -> list | None:
    """(start_s, wall_s, admitted, cached) of the steps begun in the
    window."""
    if "steps" not in run.data:
        return None
    return [s for s in run.data["steps"] if s[0] < run.seconds]


def runtime_row(run, cls: str) -> dict | None:
    rows = run.data.get("runtime_classes")
    return (rows or {}).get(cls)

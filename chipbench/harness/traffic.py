"""The one general generator: a traffic file's parameters and a seed in,
the run's inputs out.

Every seed gets the same multiset of sizes and inter-arrival gaps, drawn at
evenly spaced quantiles of the mix's distributions, in an order the seed
permutes; token ids and frame contents come from the seed. So two seeds do
the same amount of work, and the same seed gives the same inputs."""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose, from any non-negative seed."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.default_rng([int(seed), tag])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(dist: dict, n: int) -> np.ndarray:
    """n sizes at evenly spaced quantiles of ``dist``, clipped to
    [min, max] and rounded up to a multiple of ``round_to``."""
    u = _quantiles(n)
    kind = dist["dist"]
    lo, hi = dist["min"], dist["max"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = lo + u * (hi - lo)
    elif kind == "uniform_int":
        v = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    v = np.clip(np.ceil(v), lo, hi)
    step = dist.get("round_to", 1)
    return (np.ceil(v / step) * step).astype(np.int64)


# -- open-loop requests -------------------------------------------------------

@dataclass(frozen=True)
class RequestSpec:
    rid: int
    due_s: float          # offset from the window's start
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def arrivals(traffic: dict, n: int, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds): exponential gaps (Poisson arrivals) at
    evenly spaced quantiles, in the seed's order, scaled so the last
    falls half a mean gap before the window closes."""
    proc = traffic["arrivals"]["process"]
    if proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds * (n - 0.5) / n) / max(due[-1] + gaps[-1], 1e-12)


def requests(traffic: dict, seconds: float, seed: int,
             vocab: int) -> list[RequestSpec]:
    n = max(1, int(round(traffic["arrivals"]["rate_per_s"] * seconds)))
    rng = rng_for(seed, "requests")
    due = arrivals(traffic, n, seconds, rng)
    p_len = sizes(traffic["prompt_tokens"], n)[rng.permutation(n)]
    o_len = sizes(traffic["output_tokens"], n)[rng.permutation(n)]
    ids = rng_for(seed, "token_ids")
    return [RequestSpec(i, float(due[i]),
                        ids.integers(0, vocab, int(p_len[i]), dtype=np.int32),
                        int(o_len[i])) for i in range(n)]


# -- sensor frames -------------------------------------------------------------

def dvs_frames(frames: dict, hw: int, channels: int, seed: int) -> np.ndarray:
    """A pool of normalized DVS event histograms [pool, 1, hw, hw, c]:
    each frame accumulates a fixed number of events, a share of them
    around a hand-sized blob at a random place and the rest uniform
    background noise, then is divided by its largest bin."""
    rng = rng_for(seed, "frames")
    pool, n_ev = frames["pool"], frames["events_per_frame"]
    n_hand = int(round(n_ev * frames["hand_share"]))
    out = np.zeros((pool, 1, hw, hw, channels), np.float32)
    for i in range(pool):
        cy, cx = rng.uniform(hw * 0.25, hw * 0.75, 2)
        ys = np.concatenate([rng.normal(cy, frames["hand_sigma_px"], n_hand),
                             rng.uniform(0, hw, n_ev - n_hand)])
        xs = np.concatenate([rng.normal(cx, frames["hand_sigma_px"], n_hand),
                             rng.uniform(0, hw, n_ev - n_hand)])
        ys = np.clip(ys.astype(np.int64), 0, hw - 1)
        xs = np.clip(xs.astype(np.int64), 0, hw - 1)
        ch = rng.integers(0, channels, n_ev)
        np.add.at(out[i, 0], (ys, xs, ch), 1.0)
        out[i] /= max(float(out[i].max()), 1.0)
    return out


def frame_order(n_pool: int, seed: int) -> np.ndarray:
    """The order in which a closed loop sends the pool, repeated."""
    return rng_for(seed, "frame_order").permutation(n_pool)

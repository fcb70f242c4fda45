"""Run the main path once on one TPU chip and check what comes out.

    python chip_smoke.py

One process drives every phase; there is no CPU fallback, and any failed
check exits non-zero with no result line.

(a) The paper's path: the RoShamBo CNN at its published size (64x64x1,
    five conv layers) streamed layer by layer through ``NullHopExecutor``
    under the three managements, against ``RoShamBoCNN.apply`` on the chip.
(b) Weight streaming at LM scale: ``HostStreamingExecutor`` (staged,
    interrupt ring of depth 4) over the ``benchmarks/streaming_layers``
    gated-MLP stack (8 layers, d=1024, f=4096, 48 MiB of f32 per layer),
    against the same layers run from device-resident weights; plus one
    staged pack -> TX -> unpack round trip of a layer in f32 and bf16.
(c) Serving: qwen2.5-3b at full width, built as ``repro.launch.serve
    --full`` builds it. ``ServingEngine.generate`` answers a batch twice
    (greedy tokens must repeat); ``ContinuousBatchingEngine`` then completes
    submitted requests on the same device weights.

Earlier lines give each phase's check, wall times and peak device memory;
the last line of stdout is the JSON verdict with the device JAX reports.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.streaming_layers import mlp_layers  # noqa: E402
from repro.accel.nullhop import NullHopExecutor  # noqa: E402
from repro.accel.roshambo import RoShamBoCNN  # noqa: E402
from repro.core.streaming import HostStreamingExecutor  # noqa: E402
from repro.core.transfer import (  # noqa: E402
    StagedLayout,
    TransferEngine,
    TransferPolicy,
)
from repro.launch.serve import build  # noqa: E402
from repro.serve.continuous import ContinuousBatchingEngine, Request  # noqa: E402
from repro.serve.engine import ServeConfig, ServingEngine  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

MANAGEMENTS = {
    "user_level_polling": TransferPolicy.user_level_polling,
    "user_level_scheduled": TransferPolicy.user_level_scheduled,
    "kernel_level": TransferPolicy.kernel_level,
}


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def phase_roshambo(n_frames: int = 4, batch: int = 1, seed: int = 0) -> dict:
    """(a) RoShamBo frames through NullHopExecutor under each management.

    TPU matmuls and convolutions run f32 inputs through bf16 passes by
    default; the phase asks for full f32 so the streamed logits can be held
    to an f32 tolerance against the monolithic forward."""
    cnn = RoShamBoCNN()
    cfg = cnn.cfg
    params = cnn.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    frames = rng.random((n_frames, batch, cfg.input_hw, cfg.input_hw,
                         cfg.layers[0].c_in)).astype(np.float32)
    device = jax.devices()[0]
    out: dict = {"frames": n_frames, "batch": batch}
    with jax.default_matmul_precision("float32"):
        oracle = jax.jit(cnn.apply)
        refs = [np.asarray(oracle(params, jnp.asarray(f))) for f in frames]
        for name, policy in MANAGEMENTS.items():
            ex = NullHopExecutor(cnn, policy())
            try:
                probe = ex.engine.tx(frames[0])
                if ex.engine.device != device or any(
                        c.devices() != {device} for c in probe):
                    raise AssertionError(
                        f"{name}: transferred arrays are not on {device}")
                ex.run_frame(params, frames[0])  # compiles the layer steps
                t0 = time.perf_counter()
                errs = []
                for f, ref in zip(frames, refs):
                    res = ex.run_frame(params, f)
                    if res.logits.shape != ref.shape:
                        raise AssertionError(
                            f"{name}: logits {res.logits.shape} != "
                            f"{ref.shape}")
                    errs.append(_max_rel_err(res.logits, ref))
                frame_ms = (time.perf_counter() - t0) * 1e3 / n_frames
            finally:
                ex.close()
            err = max(errs)
            if not err <= 1e-4:
                raise AssertionError(
                    f"{name}: streamed logits off the oracle by {err:.3g} "
                    f"(relative to the largest logit)")
            out[name] = {"max_rel_err": err, "frame_ms": frame_ms,
                         "tx_ms": sum(l.tx_s for l in res.timing.layers) * 1e3,
                         "compute_ms": sum(l.compute_s
                                           for l in res.timing.layers) * 1e3,
                         "rx_ms": sum(l.rx_s for l in res.timing.layers) * 1e3}
    out["check"] = ("streamed logits == RoShamBoCNN.apply on the chip, "
                    "max relative error <= 1e-4, under each management; "
                    "TX chunks on the TPU device")
    return out


def phase_streaming(n_layers: int = 8, d: int = 1024, f: int = 4096,
                    tokens: int = 8, seed: int = 0) -> dict:
    """(b) Layer-streamed gated-MLP stack vs device-resident weights."""
    key = jax.random.PRNGKey(seed)
    layers = mlp_layers(n_layers, d, f, key)
    x = np.asarray(jax.random.normal(jax.random.fold_in(key, 1), (tokens, d)),
                   np.float32)
    engine = TransferEngine(TransferPolicy.kernel_level_ring(4))
    try:
        ex = HostStreamingExecutor(engine, staged=True)
        ex.run(layers, x)  # compiles, and fits the pack-vs-SG choice
        t0 = time.perf_counter()
        got, timing = ex.run(layers, x)
        frame_ms = (time.perf_counter() - t0) * 1e3

        # the staged pack path itself, at LM scale, in f32 and in bf16
        roundtrip = {}
        for dtype in (np.float32, jnp.bfloat16):
            params = [np.asarray(p).astype(dtype) for p in layers[0][1]]
            lay = StagedLayout(params)
            dev = lay.unpack(engine.tx(lay.pack(params)))
            jax.block_until_ready(dev)
            for p, dp in zip(params, dev):
                if not np.array_equal(np.asarray(dp).view(np.uint8),
                                      p.view(np.uint8)):
                    raise AssertionError(
                        f"staged {np.dtype(dtype).name} round trip changed "
                        f"a {p.shape} weight")
            roundtrip[np.dtype(dtype).name] = lay.nbytes
    finally:
        engine.close()

    y = jnp.asarray(x)
    for _name, params, apply_fn in layers:
        y = apply_fn([jax.device_put(p) for p in params], y)
    ref = np.asarray(y)
    if got.shape != ref.shape:
        raise AssertionError(f"streamed output {got.shape} != {ref.shape}")
    err = _max_rel_err(got, ref)
    if not err <= 1e-6:
        raise AssertionError(
            f"streamed output off the device-resident run by {err:.3g}")
    return {
        "layers": n_layers,
        "bytes_per_layer": sum(p.nbytes for p in layers[0][1]),
        "max_rel_err": err, "bitwise_equal": bool(np.array_equal(got, ref)),
        "frame_ms": frame_ms,
        "tx_ms": sum(l.tx_s for l in timing.layers) * 1e3,
        "compute_ms": sum(l.compute_s for l in timing.layers) * 1e3,
        "rx_ms": sum(l.rx_s for l in timing.layers) * 1e3,
        "staged_roundtrip_bytes": roundtrip,
        "check": ("streamed output == device-resident weights, max relative "
                  "error <= 1e-6; staged f32/bf16 round trip bit-exact"),
    }


def phase_serving(arch: str = "qwen2.5-3b", batch: int = 4,
                  prompt_len: int = 128, new_tokens: int = 32,
                  cb_requests: int = 4, cb_new_tokens: int = 8,
                  seed: int = 0) -> dict:
    """(c) Full-width serving: batched greedy generation, then continuous
    batching on the same device weights."""
    t0 = time.perf_counter()
    model, params = build(arch, smoke=False, seed=seed)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    cfg = model.cfg
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (batch, prompt_len), dtype=np.int32)
    max_seq = prompt_len + new_tokens + 8
    out: dict = {"arch": cfg.name, "params": n_params, "init_s": init_s,
                 "batch": batch, "prompt_len": prompt_len,
                 "new_tokens": new_tokens}

    eng = ServingEngine(model, params, ServeConfig(
        max_batch=batch, max_seq=max_seq, temperature=0.0))
    try:
        runs = []
        for _ in range(2):
            t0 = time.perf_counter()
            res = eng.generate(prompts, max_new_tokens=new_tokens)
            wall = time.perf_counter() - t0
            runs.append((np.stack([r.tokens for r in res]), wall, res[0]))
    finally:
        eng.close()
    (toks1, wall1, _), (toks2, wall2, warm) = runs
    if toks1.shape != (batch, new_tokens):
        raise AssertionError(f"generated {toks1.shape}, wanted "
                             f"{(batch, new_tokens)}")
    if toks1.min() < 0 or toks1.max() >= cfg.vocab:
        raise AssertionError("generated a token outside the vocabulary")
    if not np.array_equal(toks1, toks2):
        raise AssertionError("greedy tokens differ between two calls")
    out["generate"] = {"first_call_s": wall1, "second_call_s": wall2,
                       "prefill_ms": warm.prefill_s * 1e3,
                       "decode_ms": warm.decode_s * 1e3,
                       "decode_tokens_per_s_per_seq": warm.tokens_per_s}

    cbe = ContinuousBatchingEngine(model, params, n_slots=batch,
                                   max_seq=max_seq)
    try:
        for i in range(cb_requests):
            decision = cbe.submit(Request(rid=i, prompt=prompts[i % batch],
                                          max_new_tokens=cb_new_tokens))
            if not decision.admitted:
                raise AssertionError(f"request {i} shed: {decision}")
        t0 = time.perf_counter()
        done = cbe.run_to_completion(max_steps=16 * cb_new_tokens)
        cb_s = time.perf_counter() - t0
    finally:
        cbe.close()
    if sorted(r.rid for r in done) != list(range(cb_requests)):
        raise AssertionError(f"completed {sorted(r.rid for r in done)} of "
                             f"{cb_requests} requests")
    for r in done:
        t = np.asarray(r.tokens)
        if len(t) != cb_new_tokens or t.min() < 0 or t.max() >= cfg.vocab:
            raise AssertionError(f"request {r.rid} tokens {r.tokens}")
    same_first = sum(int(r.tokens[0] == toks1[r.rid % batch, 0])
                     for r in done)
    out["continuous"] = {"requests": cb_requests, "steps": cbe.steps,
                         "wall_s": cb_s,
                         "first_token_agrees_with_generate": same_first}
    out["check"] = ("greedy tokens identical across two generate calls, "
                    "in vocabulary; every continuous-batching request "
                    "completed with its tokens")
    return out


def main() -> int:
    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is "
              f"{device.platform}); nothing is run on a CPU fallback",
              file=sys.stderr)
        return 1
    print(json.dumps({"jax": jax.__version__, "platform": device.platform,
                      "device_kind": device.device_kind,
                      "device_count": len(jax.devices())}), flush=True)
    for name, phase in (("a_roshambo", phase_roshambo),
                        ("b_streaming", phase_streaming),
                        ("c_serving", phase_serving)):
        t0 = time.perf_counter()
        info = phase()
        info = {"phase": name, "wall_s": time.perf_counter() - t0,
                "peak_bytes_in_use": _peak_bytes(), **info}
        print(json.dumps(info), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

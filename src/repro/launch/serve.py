"""Serving driver: batched generation with a policy-driven engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 32
"""

from __future__ import annotations

import argparse
from typing import Any

import jax
import numpy as np

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.models.api import Model, build_model
from repro.serve.engine import ServeConfig, ServingEngine
from repro.utils.compile_cache import enable_compile_cache


def build(arch: str, *, smoke: bool, seed: int = 0) -> tuple[Model, Any]:
    """The model and its random weights, made on the device from ``seed``
    (``smoke`` selects the reduced same-family config). The init is one
    compiled program, so no f32 temporary of a full-width weight is held."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    model, params = build(args.arch, smoke=args.smoke)
    cfg = model.cfg
    max_seq = args.prompt_len + args.new_tokens + 8
    eng = ServingEngine(model, params,
                        ServeConfig(max_batch=args.batch, max_seq=max_seq,
                                    temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len),
                           dtype=np.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["patch_embeds"] = rng.standard_normal(
            (args.batch, cfg.n_prefix_tokens, cfg.d_model)).astype(np.float32)
    if cfg.family == "audio":
        extra["frames"] = rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)
    res = eng.generate(prompts, max_new_tokens=args.new_tokens,
                       extra_inputs=extra or None)
    for i, r in enumerate(res):
        print(f"req{i}: prefill={r.prefill_s*1e3:.1f}ms "
              f"decode={r.decode_s*1e3:.1f}ms tok/s={r.tokens_per_s:.1f} "
              f"tokens={r.tokens[:8].tolist()}...")


if __name__ == "__main__":
    main()

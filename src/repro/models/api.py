"""Public model API: one entry point for every assigned architecture.

``build_model(cfg)`` returns a :class:`Model` of pure functions:

- ``init(key) -> params``
- ``forward(params, batch) -> (logits, aux)``           (teacher-forced)
- ``loss(params, batch) -> (scalar, metrics)``
- ``prefill(params, batch, s_max) -> (logits, cache)``
- ``decode(params, token, cache) -> (logits, cache)``
- ``init_cache(batch, s_max) -> cache``                 (for decode dry-runs)

Batches are dicts. Keys by family:
- dense/moe/ssm/hybrid: tokens [B,S], labels [B,S]
- vlm: tokens [B,S_text], patch_embeds [B,n_prefix,D], labels [B,S_text]
- audio: frames [B,S_enc,D], tokens [B,S_dec], labels [B,S_dec]
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, lm
from repro.models.config import ModelConfig


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab: int) -> tuple[jax.Array, jax.Array]:
    """Mean next-token CE over valid positions (labels >= 0), and accuracy.

    logits: [B,S,Vp] float32; labels: [B,S] int32 (-1 = ignore)."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    denom = jnp.maximum(valid.sum(), 1)
    acc = ((logits.argmax(-1) == safe) & valid).sum() / denom
    return nll.sum() / denom, acc


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    forward: Callable[..., Any]
    loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    init_cache: Callable[..., Any]
    # prefill/decode drive their own programs from the host, layer by layer
    # (repro.models.moonlight): callers run them as they are, not under jit
    host_loop: bool = False


def build_model(cfg: ModelConfig) -> Model:
    fam = cfg.family
    if fam == "mla_moe":
        raise NotImplementedError(
            f"{cfg.name}: served with its experts in host memory; build it "
            f"with repro.models.moonlight.ExpertOffload(...).model()")

    # ---- forward ----
    if fam == "audio":
        def fwd(params, batch):
            return encdec.forward(cfg, params, batch["frames"], batch["tokens"])
    elif fam == "hybrid":
        def fwd(params, batch):
            return hybrid.forward(cfg, params, batch["tokens"])
    elif fam == "vlm":
        def fwd(params, batch):
            return lm.forward(cfg, params, batch["tokens"],
                              prefix_embeds=batch["patch_embeds"])
    else:
        def fwd(params, batch):
            return lm.forward(cfg, params, batch["tokens"])

    # ---- loss ----
    def loss(params, batch):
        logits, aux = fwd(params, batch)
        labels = batch["labels"]
        if fam == "vlm":  # loss only over text positions (after image prefix)
            logits = logits[:, cfg.n_prefix_tokens:]
        ce, acc = cross_entropy(logits, labels, cfg.vocab_padded)
        total = ce + cfg.router_aux_coef * aux
        return total, {"loss": ce, "aux": aux, "acc": acc}

    # ---- init ----
    if fam == "audio":
        init = lambda key: encdec.init_params(key, cfg)  # noqa: E731
    elif fam == "hybrid":
        init = lambda key: hybrid.init_params(key, cfg)  # noqa: E731
    else:
        init = lambda key: lm.init_params(key, cfg)  # noqa: E731

    # ---- prefill / decode ----
    if fam == "audio":
        def pre(params, batch, s_max):
            return encdec.prefill(cfg, params, batch["frames"],
                                  batch["tokens"], s_max)

        def dec(params, token, cache):
            return encdec.decode_step(cfg, params, token, cache)

        def icache(batch_size, s_max, s_enc=None):
            return encdec.init_dec_cache(cfg, batch_size, s_max,
                                         s_enc or s_max)
    elif fam == "hybrid":
        def pre(params, batch, s_max):
            return hybrid.prefill(cfg, params, batch["tokens"], s_max)

        def dec(params, token, cache):
            return hybrid.decode_step(cfg, params, token, cache)

        def icache(batch_size, s_max, s_enc=None):
            return hybrid.init_cache(cfg, batch_size, s_max)
    else:
        def pre(params, batch, s_max):
            pe = batch.get("patch_embeds") if fam == "vlm" else None
            s_tok = batch["tokens"].shape[1]
            if (cfg.prefill_chunk and pe is None
                    and s_tok % cfg.prefill_chunk == 0
                    and s_tok > cfg.prefill_chunk):
                return lm.prefill_chunked(cfg, params, batch["tokens"],
                                          s_max, chunk=cfg.prefill_chunk)
            return lm.prefill(cfg, params, batch["tokens"], s_max,
                              prefix_embeds=pe)

        def dec(params, token, cache):
            return lm.decode_step(cfg, params, token, cache)

        def icache(batch_size, s_max, s_enc=None):
            return lm.init_cache(cfg, batch_size, s_max)

    return Model(cfg=cfg, init=init, forward=fwd, loss=loss, prefill=pre,
                 decode=dec, init_cache=icache)


def input_specs(cfg: ModelConfig, cell, *, for_init: bool = False) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    No device allocation — shardable, weak-type-correct. ``decode`` cells
    describe the single-token step against a seq_len cache (built separately
    via cache_specs)."""
    b, s = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct
    if cell.kind == "decode":
        if cfg.family == "audio":
            return {"tokens": sds((b, 1), i32)}
        return {"tokens": sds((b, 1), i32)}
    if cfg.family == "audio":
        return {
            "frames": sds((b, s, cfg.d_model), f),
            "tokens": sds((b, s), i32),
            "labels": sds((b, s), i32),
        }
    if cfg.family == "vlm":
        s_text = s - cfg.n_prefix_tokens
        return {
            "tokens": sds((b, s_text), i32),
            "patch_embeds": sds((b, cfg.n_prefix_tokens, cfg.d_model), f),
            "labels": sds((b, s_text), i32),
        }
    return {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}


def cache_specs(cfg: ModelConfig, batch: int, s_max: int) -> Any:
    """ShapeDtypeStruct pytree matching init_cache output (for dry-runs)."""
    model = build_model(cfg)
    return jax.eval_shape(lambda: model.init_cache(batch, s_max))

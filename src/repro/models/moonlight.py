"""Moonlight-16B-A3B (moonshotai): the DeepSeek-V3 block (arXiv:2412.19437)
without multi-token prediction, served with its routed experts in host
memory.

Every layer is latent attention (:mod:`repro.models.layers.mla`) then an
FFN: a gated-SiLU MLP of width ``d_ff`` in the leading
``first_dense_layers``, and in the rest a MoE of ``n_experts`` routed
experts of width ``d_expert``, ``top_k`` a token (sigmoid scores, bias-
corrected choice: :func:`repro.models.layers.moe.sigmoid_route`), plus
one always-on shared MLP of width ``n_shared_experts * d_expert``. RMS
norms use ``cfg.rms_norm_eps``; embeddings and head are untied. The
residual stream is float32; matmul inputs are in ``cfg.dtype``.

The routed experts do not fit in the device's memory, so they live as
host arrays and :class:`ExpertOffload` runs a step layer by layer from the
host. Per MoE layer: one program runs attention and the router; the host
receives the routed expert ids (span ``repro.moe.route``); the shared MLP
is dispatched; the distinct routed experts stream to the device
(:meth:`repro.core.streaming.HostStreamingExecutor.stream_routed`), each
applied as it lands; the layer ends when its output is complete (span
``repro.moe.experts``; counter ``repro.moe.experts_moved``, the distinct
experts moved). Experts given as device arrays are used in place: the
resident path, for tests at small sizes.

Weights (``params``, on the device): ``embed`` [V, D], ``lm_head`` [D, V],
``final_norm``, and ``layers``, one dict per layer: ``ln1``, ``attn``,
``ln2``, then ``mlp`` {``wi`` [D, 2F], ``wo`` [F, D]} (dense) or ``moe``
{``router`` [D, E], ``bias`` [E], ``shared`` {``wi``, ``wo``}}. Experts
(``experts``, one dict per MoE layer): ``wi`` [E, D, 2Fe] (gate columns,
then up) and ``wo`` [E, Fe, D]."""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.models.config import ModelConfig
from repro.models.layers import mla
from repro.models.layers.moe import expert_add, gated_silu, sigmoid_route
from repro.models.layers.norm import rms_norm
from repro.utils import spans
from repro.utils.spans import span

EXPERTS_MOVED = "repro.moe.experts_moved"
F32 = jnp.float32


class LatentCache(NamedTuple):
    """Per layer, the latent ``c`` [B, S_max, r] and RoPE key ``kr``
    [B, S_max, dr]; ``length`` [L] or [L, B]: positions cached."""

    c: tuple
    kr: tuple
    length: jax.Array


class Call(NamedTuple):
    """One recorded prefill or decode: its logits (on the device) and, per
    MoE layer, the routed expert ids of its real tokens (host)."""

    kind: str
    logits: jax.Array
    routes: list


# ---------------------------------------------------------------------------
# weights and cache
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> tuple[dict, list[dict]]:
    """Random weights: (device params, host experts)."""
    dt = jnp.dtype(cfg.dtype)
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = iter(jax.random.split(key, 8 * cfg.n_layers + 3))

    def w(shape):
        return (jax.random.normal(next(ks), shape)
                / math.sqrt(shape[-2])).astype(dt)

    def scale(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), F32)

    layers, experts = [], []
    for li in range(cfg.n_layers):
        lp = {"ln1": {"scale": scale(d)},
              "attn": mla.mla_params(next(ks), cfg, dt),
              "ln2": {"scale": scale(d)}}
        if li < cfg.first_dense_layers:
            lp["mlp"] = {"wi": w((d, 2 * cfg.d_ff)), "wo": w((cfg.d_ff, d))}
        else:
            fs = cfg.n_shared_experts * fe
            lp["moe"] = {"router": w((d, e)), "bias": jnp.zeros((e,), F32),
                         "shared": {"wi": w((d, 2 * fs)), "wo": w((fs, d))}}
            experts.append({"wi": np.asarray(w((e, d, 2 * fe))),
                            "wo": np.asarray(w((e, fe, d)))})
        layers.append(lp)
    params = {"embed": w((cfg.vocab, d)), "lm_head": w((d, cfg.vocab)),
              "final_norm": {"scale": scale(d)}, "layers": layers}
    return params, experts


def init_cache(cfg: ModelConfig, batch: int, s_max: int) -> LatentCache:
    dt = jnp.dtype(cfg.dtype)
    n = cfg.n_layers
    return LatentCache(
        tuple(jnp.zeros((batch, s_max, cfg.kv_lora_rank), dt)
              for _ in range(n)),
        tuple(jnp.zeros((batch, s_max, cfg.qk_rope_head_dim), dt)
              for _ in range(n)),
        jnp.zeros((n,), jnp.int32))


# ---------------------------------------------------------------------------
# the programs a step dispatches (one compiled program per shape; the
# names are what a device trace shows, ``jit_moonlight_*``)
# ---------------------------------------------------------------------------

@jax.jit
def moonlight_embed(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    return embed[tokens].astype(F32)


def _attention(cfg, s_max, lp, x, c_cache, kr_cache, lens):
    """Attention with its residual. Prefill (``c_cache`` None): the s new
    tokens from position 0, into a fresh cache of ``s_max`` positions.
    Decode: one token a row at position ``lens`` [B]."""
    dt = jnp.dtype(cfg.dtype)
    b, s, _ = x.shape
    h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_norm_eps)
    if c_cache is None:
        pos = jnp.arange(s)[None]
    else:
        pos = lens[:, None]
    q_n, q_r, c, kr = mla.project(lp["attn"], h, cfg, pos)
    c, kr = c.astype(dt), kr.astype(dt)
    if c_cache is None:
        ok = jnp.tril(jnp.ones((s, s), bool))[None]
        out = mla.attend(lp["attn"], cfg, q_n, q_r, c, kr, ok)
        c_cache = jnp.zeros((b, s_max, c.shape[-1]), dt).at[:, :s].set(c)
        kr_cache = jnp.zeros((b, s_max, kr.shape[-1]), dt).at[:, :s].set(kr)
    else:
        rows = jnp.arange(b)
        c_cache = c_cache.at[rows, lens].set(c[:, 0])
        kr_cache = kr_cache.at[rows, lens].set(kr[:, 0])
        t = jnp.arange(c_cache.shape[1])
        ok = (t[None, :] <= lens[:, None])[:, None]
        out = mla.attend(lp["attn"], cfg, q_n, q_r, c_cache, kr_cache, ok)
    return x + out, c_cache, kr_cache


@functools.partial(jax.jit, static_argnums=(0, 1))
def moonlight_dense_layer(cfg, s_max, lp, x, c_cache, kr_cache, lens):
    x, c_cache, kr_cache = _attention(cfg, s_max, lp, x, c_cache, kr_cache,
                                      lens)
    h = rms_norm(x, lp["ln2"]["scale"], cfg.rms_norm_eps)
    return x + gated_silu(h, lp["mlp"]["wi"], lp["mlp"]["wo"]), \
        c_cache, kr_cache


@functools.partial(jax.jit, static_argnums=(0, 1))
def moonlight_route(cfg, s_max, lp, x, c_cache, kr_cache, lens):
    """A MoE layer's first half: attention, the FFN's norm, the router."""
    x, c_cache, kr_cache = _attention(cfg, s_max, lp, x, c_cache, kr_cache,
                                      lens)
    h = rms_norm(x, lp["ln2"]["scale"], cfg.rms_norm_eps)
    ids, w = sigmoid_route(lp["moe"]["router"], lp["moe"]["bias"], h,
                           top_k=cfg.top_k, scale=cfg.routed_scaling)
    return x, h.astype(cfg.dtype), ids, w, c_cache, kr_cache


@jax.jit
def moonlight_shared(x, h, wi, wo):
    return x + gated_silu(h, wi, wo)


@jax.jit
def moonlight_expert(x, h, ids, w, e, wi, wo):
    return expert_add(x, h, ids, w, e, wi, wo)


@functools.partial(jax.jit, static_argnums=(0,))
def moonlight_head(cfg, scale, head, x, last):
    """Logits [B, 1, V] (float32) at position ``last``."""
    x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    h = rms_norm(x, scale, cfg.rms_norm_eps)
    return jnp.einsum("bsd,dv->bsv", h.astype(head.dtype), head,
                      preferred_element_type=F32)


# ---------------------------------------------------------------------------
# the host loop
# ---------------------------------------------------------------------------

class ExpertOffload:
    """Prefill and decode, layer by layer from the host, with the routed
    experts in host memory streamed through ``executor`` (a
    :class:`~repro.core.streaming.HostStreamingExecutor`), or used in place
    where ``experts`` holds device arrays (``executor`` None).

    ``prefill_pad``: prompts are padded at the end to a multiple of it, so
    that every prompt of up to that length runs the same programs; the
    padding is never attended to, routed from or cached as valid.

    ``calls``: set to a list to record every prefill and decode
    (:class:`Call`)."""

    def __init__(self, cfg: ModelConfig, experts: list[dict],
                 executor: Any = None, *, prefill_pad: int = 0):
        if cfg.family != "mla_moe":
            raise ValueError(f"{cfg.name}: family {cfg.family!r}, not mla_moe")
        if len(experts) != cfg.n_layers - cfg.first_dense_layers:
            raise ValueError("one experts dict per MoE layer")
        self.cfg = cfg
        self.experts = experts
        self.executor = executor
        self.prefill_pad = prefill_pad
        self.calls: list[Call] | None = None
        self._eids: list[jax.Array] = []

    def model(self) -> Model:
        """The serving engine's view: prefill and decode drive their own
        programs from the host (``Model.host_loop``)."""
        cfg = self.cfg
        return Model(cfg=cfg, init=lambda key: init_params(key, cfg)[0],
                     forward=None, loss=None, prefill=self.prefill,
                     decode=self.decode, host_loop=True,
                     init_cache=lambda b, s_max, s_enc=None:
                         init_cache(cfg, b, s_max))

    # -- one MoE layer's second half ----------------------------------------
    def _rx(self, a: jax.Array) -> np.ndarray:
        if self.executor is None:
            return np.asarray(a)
        return np.asarray(self.executor.engine.rx([a])[0])

    def _eid(self, e: int) -> jax.Array:
        while len(self._eids) <= e:  # device scalars, made once
            self._eids.append(jnp.asarray(len(self._eids), jnp.int32))
        return self._eids[e]

    def _experts(self, m: int, lp: dict, x, h, ids, w,
                 chosen: np.ndarray) -> jax.Array:
        sel = [int(e) for e in np.unique(chosen)]
        spans.count(EXPERTS_MOVED, len(sel))
        ex = self.experts[m]
        with span("repro.moe.experts"):
            sh = lp["moe"]["shared"]
            x = moonlight_shared(x, h, sh["wi"], sh["wo"])

            def apply(j: int, dev: list) -> None:
                nonlocal x
                x = moonlight_expert(x, h, ids, w, self._eid(sel[j]), *dev)

            if self.executor is None:
                for j, e in enumerate(sel):
                    apply(j, [ex["wi"][e], ex["wo"][e]])
            else:
                self.executor.stream_routed(
                    [(ex["wi"][e], ex["wo"][e]) for e in sel], apply)
            x.block_until_ready()
        return x

    def _layers(self, params, x, caches, lens, n_real: int, s_max: int):
        """Every layer; returns x, the new per-layer caches and routes.
        Prefill: ``caches`` holds (None, None) and ``s_max`` is the cache
        length to make; decode: ``s_max`` is 0."""
        cfg = self.cfg
        cs, krs, routes = [], [], []
        for li, lp in enumerate(params["layers"]):
            c, kr = caches[li]
            if li < cfg.first_dense_layers:
                x, c, kr = moonlight_dense_layer(cfg, s_max, lp, x, c, kr,
                                                 lens)
            else:
                with span("repro.moe.route"):
                    x, h, ids, w, c, kr = moonlight_route(
                        cfg, s_max, lp, x, c, kr, lens)
                    chosen = self._rx(ids)[:, :n_real]
                routes.append(chosen)
                x = self._experts(li - cfg.first_dense_layers, lp, x, h,
                                  ids, w, chosen)
            cs.append(c)
            krs.append(kr)
        return x, cs, krs, routes

    def _record(self, kind: str, logits, routes) -> None:
        if self.calls is not None:
            self.calls.append(Call(kind, logits, routes))

    # -- the serving entry points -------------------------------------------
    def prefill(self, params: dict, batch: dict, s_max: int):
        """batch["tokens"] [1, S] -> (logits [1, 1, V] at S - 1, cache)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        pad = self.prefill_pad or s
        padded = -(-s // pad) * pad
        if padded > s_max:
            raise ValueError(f"prompt of {s} tokens pads to {padded} > "
                             f"{s_max} cache positions")
        if padded > s:
            tokens = jnp.pad(tokens, ((0, 0), (0, padded - s)))
        x = moonlight_embed(params["embed"], tokens)
        none = [(None, None)] * self.cfg.n_layers
        x, cs, krs, routes = self._layers(params, x, none, None, s, s_max)
        logits = moonlight_head(self.cfg, params["final_norm"]["scale"],
                                params["lm_head"], x, s - 1)
        self._record("prefill", logits, routes)
        length = jnp.full((self.cfg.n_layers,), s, jnp.int32)
        return logits, LatentCache(tuple(cs), tuple(krs), length)

    def decode(self, params: dict, token: jax.Array, cache: LatentCache):
        """token [B, 1] at each row's ``cache.length`` -> (logits [B, 1, V],
        cache advanced by one)."""
        lens = cache.length[0]
        x = moonlight_embed(params["embed"], token)
        x, cs, krs, routes = self._layers(
            params, x, list(zip(cache.c, cache.kr)), lens, 1, 0)
        logits = moonlight_head(self.cfg, params["final_norm"]["scale"],
                                params["lm_head"], x, 0)
        self._record("decode", logits, routes)
        return logits, LatentCache(tuple(cs), tuple(krs), cache.length + 1)

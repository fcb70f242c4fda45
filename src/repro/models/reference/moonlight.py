"""A plain reference for Moonlight-16B-A3B (the DeepSeek-V3 block,
arXiv:2412.19437, without multi-token prediction), written from the
published description and modelling code in ``jax.numpy``, float32,
every product at ``highest`` precision. It imports nothing of the program
and reads the published config keys (``hidden_size``, ``kv_lora_rank``,
``n_routed_experts``, ...) from a plain dict.

- Multi-head latent attention in the expanded form: at every position the
  keys and values are built from the latent, k = [c W_kb ; k_rope] and
  v = c W_vb, and attention is causal softmax(q.k / sqrt(dn + dr)) v.
- RoPE as the published code applies it: the RoPE dimensions are read as
  interleaved pairs, de-interleaved (view(d/2, 2).transpose), then rotated
  by halves with cos/sin of position x theta^(-2i/d).
- The router as published (``noaux_tc``): sigmoid scores in float32, a
  correction bias added for the choice only, group-limited selection
  (``n_group``, ``topk_group``), the top ``num_experts_per_tok`` experts,
  weights from the unbiased scores, normalised (``norm_topk_prob``) and
  scaled by ``routed_scaling_factor``.
- The routed experts' output is every expert's gated-SiLU FFN over every
  token, weighted by that token's routing weight (0 where not chosen):
  nothing is dropped. The shared experts are one MLP of width
  ``n_shared_experts * moe_intermediate_size`` on the same input.

Departures, each for the comparison's sake and none in the mathematics:

- ``force``: a layer may be given the experts to combine (a served run's
  choice). The weights are still this reference's own scores of those
  experts, normalised and scaled; its own choice and the margin by which
  a forced expert falls short of it (``gap``) are returned beside, so a
  choice that differs is counted and measured, never hidden.
- ``fp8``: the control, one precision step below bf16: both operands of
  every product rounded to float8 e4m3 with one scale per tensor.
- Weights are taken in the layout the served model keeps (gate and up
  projections side by side in ``wi``) and widened to float32 where used.
- The batch is padded at the end; causal attention never looks at the
  padding."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(a: jax.Array) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec: str, a, b, fp8: bool) -> jax.Array:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * jnp.asarray(scale, jnp.float32)


def rope(x, theta):
    """x [N, P, H, d] at positions 0..P-1, as the published code does it."""
    n, p, h, d = x.shape
    x = x.reshape(n, p, h, d // 2, 2).swapaxes(-1, -2).reshape(n, p, h, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = jnp.arange(p, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([f, f], -1)[None, :, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def attention(c: dict, p: dict, h, fp8: bool = False):
    n, s, _ = h.shape
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    q = _ein("npd,dk->npk", h, p["wq"], fp8).reshape(n, s, nh, dn + dr)
    ckv = _ein("npd,dk->npk", h, p["wkv_a"], fp8)
    lat = rms(ckv[..., :r], p["kv_norm"]["scale"], c["rms_norm_eps"])
    kv = _ein("npr,rk->npk", lat, p["wkv_b"], fp8).reshape(n, s, nh, dn + dv)
    q_pe = rope(q[..., dn:], c["rope_theta"])
    k_pe = rope(ckv[..., None, r:], c["rope_theta"])
    qf = jnp.concatenate([q[..., :dn], q_pe], -1)
    kf = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (n, s, nh, dr))], -1)
    sc = _ein("nqhd,nkhd->nhqk", qf, kf, fp8) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _ein("nhqk,nkhd->nqhd", pr, kv[..., dn:], fp8)
    return _ein("npk,kd->npd", o.reshape(n, s, nh * dv), p["wo"], fp8)


def mlp(h, wi, wo, fp8: bool = False):
    g = _ein("npd,df->npf", h, wi, fp8)
    f = g.shape[-1] // 2
    return _ein("npf,fd->npd", jax.nn.silu(g[..., :f]) * g[..., f:], wo, fp8)


def gate(c: dict, p: dict, h, force=None, fp8: bool = False):
    """(ids [N, P, K] combined, weights, own choice, gap). ``gap``: how far
    the weakest combined expert's choice score lies below the K-th best
    (0 where the combined set is the own choice)."""
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    g, kg = c["n_group"], c["topk_group"]
    scores = jax.nn.sigmoid(_ein("npd,de->npe", h, p["router"], fp8))
    choice = scores + jnp.asarray(p["bias"], jnp.float32)
    top2 = jax.lax.top_k(choice.reshape(*choice.shape[:-1], g, e // g), 2)[0]
    groups = jax.lax.top_k(top2.sum(-1), kg)[1]
    keep = jax.nn.one_hot(groups, g).sum(-2) > 0
    choice = jnp.where(jnp.repeat(keep, e // g, axis=-1), choice, -jnp.inf)
    best, own = jax.lax.top_k(choice, k)
    ids = own if force is None else jnp.asarray(force, jnp.int32)
    gap = jnp.maximum(best[..., -1] - jnp.take_along_axis(
        choice, ids, -1).min(-1), 0.0)
    w = jnp.take_along_axis(scores, ids, -1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * c["routed_scaling_factor"], own, gap


def routed(ex: dict, h, ids, w, fp8: bool = False):
    """Every expert over every token, weighted by the routing weights."""
    def body(acc, xs):
        wi, wo, j = xs
        wj = jnp.sum(jnp.where(ids == j, w, 0.0), -1)
        return acc + wj[..., None] * mlp(h, wi, wo, fp8), None

    n_e = ex["wi"].shape[0]
    out, _ = jax.lax.scan(body, jnp.zeros(h.shape, jnp.float32),
                          (ex["wi"], ex["wo"], jnp.arange(n_e)))
    return out


def layer(c: dict, lp: dict, ex: dict | None, x, force=None,
          fp8: bool = False):
    """One decoder layer over x [N, P, D]; returns (x, own ids, gap), the
    last two None for a dense layer."""
    eps = c["rms_norm_eps"]
    x = x + attention(c, lp["attn"], rms(x, lp["ln1"]["scale"], eps), fp8)
    h = rms(x, lp["ln2"]["scale"], eps)
    if ex is None:
        return x + mlp(h, lp["mlp"]["wi"], lp["mlp"]["wo"], fp8), None, None
    ids, w, own, gap = gate(c, lp["moe"], h, force, fp8)
    sh = lp["moe"]["shared"]
    y = mlp(h, sh["wi"], sh["wo"], fp8) + routed(ex, h, ids, w, fp8)
    return x + y, own, gap


def embed(params: dict, tokens):
    return jnp.asarray(params["embed"], jnp.float32)[tokens]


def logits(c: dict, params: dict, x, fp8: bool = False):
    h = rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return _ein("npd,dv->npv", h, params["lm_head"], fp8)


def forward(c: dict, params: dict, experts: list, tokens, force=None,
            fp8: bool = False):
    """tokens [N, P] -> (logits [N, P, V], own ids and gaps per MoE layer).
    ``force``: per MoE layer, the ids to combine, or None."""
    x = embed(params, tokens)
    own, gaps = [], []
    for li, lp in enumerate(params["layers"]):
        m = li - c["first_k_dense_replace"]
        ex = experts[m] if m >= 0 else None
        f = force[m] if force is not None and m >= 0 else None
        x, o, g = layer(c, lp, ex, x, f, fp8)
        if o is not None:
            own.append(o)
            gaps.append(g)
    return logits(c, params, x, fp8), own, gaps

"""Plain references, written from the published descriptions in
``jax.numpy`` and float32 at ``highest`` matmul precision, importing
nothing of the program; and their controls, the same computation one
precision step lower (f32 in three bf16 passes for the f32 CNN, float8 for
the bf16 LM), which the comparison has to reject.

- :func:`cnn_logits`: the RoShamBo CNN (3x3 SAME convs, ReLU, 2x2 max-pool,
  FC head on the NHWC-flattened features).
- :func:`lm_logits`: a dense decoder (RMS norm, GQA with rotary positions
  on split halves, causal sliding-window attention, gated-SiLU MLP) over
  one padded sequence, scanned layer by layer; weights are kept in the
  type they are served in and widened to f32 one layer at a time."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


# -- RoShamBo CNN ---------------------------------------------------------------

def _three_pass(op, a: jax.Array, b: jax.Array) -> jax.Array:
    """``op`` (bilinear, at ``highest``) as precision ``high`` computes f32
    on a TPU: each operand split into a bf16 head and a bf16 tail, the
    tail x tail term dropped. Written out, so that it reads the same on
    every backend (the CPU ignores the precision asked of a matmul)."""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo
    ah, al = split(a)
    bh, bl = split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def cnn_logits(cfg: dict, params: dict, x: jax.Array,
               control: bool = False) -> jax.Array:
    """x: [N, H, W, C] f32 -> [N, n_classes] f32. ``control`` computes every
    conv and the FC head in three bf16 passes (precision ``high``) instead
    of full f32."""
    def conv(a, w):
        return jax.lax.conv_general_dilated(
            a, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST)

    def dot(a, w):
        return jnp.dot(a, w, precision=HIGHEST)

    if control:
        conv = functools.partial(_three_pass, conv)
        dot = functools.partial(_three_pass, dot)
    x = x.astype(jnp.float32)
    for spec in cfg["layers"]:
        p = params[spec["name"]]
        x = jnp.maximum(conv(x, p["w"].astype(jnp.float32)) + p["b"], 0.0)
        if spec["pool"]:
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    x = x.reshape(x.shape[0], -1)
    return dot(x, params["fc"]["w"]) + params["fc"]["b"]


def rel_err(got, ref) -> float:
    """Largest |got - ref| over the largest |ref|, per row; the worst row."""
    import numpy as np
    got = np.asarray(got, np.float64).reshape(len(ref), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    err = np.abs(got - ref).max(1) / np.maximum(np.abs(ref).max(1), 1e-30)
    return float(err.max())


# -- dense decoder LM ----------------------------------------------------------

def _fp8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [S, H, Dh]; rotate the first half against the second."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # [S, Dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lm_logits(c: dict, params: dict, tokens: jax.Array,
              fp8: bool = False) -> jax.Array:
    """tokens: [S] int32 -> logits [S, vocab] f32. ``fp8`` is the control:
    both operands of every matmul rounded to float8 e4m3."""
    q8 = _fp8 if fp8 else (lambda a: a)

    def mm(a, b):
        return jnp.dot(q8(a), q8(b.astype(jnp.float32)), precision=HIGHEST)

    d, h = c["hidden_size"], c["num_attention_heads"]
    hkv = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    window = c.get("sliding_window") or 0
    s = tokens.shape[0]
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok = ok & (pos[:, None] - pos[None, :] < window)

    def layer(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(mm(a, p["attn"]["wq"]).reshape(s, h, hd), theta)
        k = _rope(mm(a, p["attn"]["wk"]).reshape(s, hkv, hd), theta)
        v = mm(a, p["attn"]["wv"]).reshape(s, hkv, hd)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q8(q), q8(k),
                        precision=HIGHEST) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q8(pr), q8(v), precision=HIGHEST)
        x = x + mm(o.reshape(s, h * hd), p["attn"]["wo"])
        m = mm(_rms(x, p["ln2"]["scale"], eps), p["mlp"]["wi"])
        gate, up = m[:, : m.shape[1] // 2], m[:, m.shape[1] // 2:]
        return x + mm(jax.nn.silu(gate) * up, p["mlp"]["wo"]), None

    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["lm_head"])


def served_gaps(logits: jax.Array, target: jax.Array) -> jax.Array:
    """Per position, how far the logit of ``target`` lies below the best
    (0 where target < 0: a position not compared)."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, jnp.maximum(target, 0)[:, None],
                              -1)[:, 0]
    return jnp.where(target >= 0, best - got, 0.0)


def lm_gap(c: dict, params: dict, tokens: jax.Array, target: jax.Array,
           control: bool = False) -> jax.Array:
    """Widest gap of the served tokens ``target`` under the reference.
    ``control`` puts the float8 control in the program's place: at every
    compared position it serves the token the control puts first."""
    ref = lm_logits(c, params, tokens)
    if control:
        low = lm_logits(c, params, tokens, fp8=True)
        target = jnp.where(target >= 0, low.argmax(-1), -1)
    return served_gaps(ref, target).max()

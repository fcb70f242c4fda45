"""Seeded random weights, made on the device in one jitted call, in the
layout the program's entry points take. The run and its reference build
them with the same function from the same seed; the reference takes
nothing that the program made."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(
        jnp.asarray(data), impl="threefry2x32"), stream)


# -- CNN: {"<layer>": {"w": [k,k,cin,cout], "b": [cout]}, "fc": {...}} --------

def cnn_shapes(cfg: dict) -> dict:
    shapes, hw, c_in = {}, cfg["input_hw"], cfg["input_channels"]
    for spec in cfg["layers"]:
        k, c_out = spec["kernel"], spec["c_out"]
        shapes[spec["name"]] = {"w": (k, k, c_in, c_out), "b": (c_out,)}
        hw, c_in = (hw // 2 if spec["pool"] else hw), c_out
    shapes["fc"] = {"w": (hw * hw * c_in, cfg["n_classes"]),
                    "b": (cfg["n_classes"],)}
    return shapes


def cnn_weights(cfg: dict, seed: int) -> dict:
    """He-scaled weights and small random biases, f32, on the device."""
    shapes = cnn_shapes(cfg)
    dt = jnp.dtype(cfg["dtype"])

    def make(key):
        out = {}
        for i, (name, s) in enumerate(sorted(shapes.items())):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = math.prod(s["w"][:-1])
            out[name] = {
                "w": (jax.random.normal(kw, s["w"]) *
                      math.sqrt(2.0 / fan_in)).astype(dt),
                "b": (0.05 * jax.random.normal(kb, s["b"])).astype(dt)}
        return out

    return jax.jit(make)(key_for(seed, 0))


# -- dense decoder LM, stacked [L, ...] blocks ---------------------------------

def lm_shapes(c: dict) -> dict:
    """Leaf shapes and dtypes of the program's dense-decoder layout."""
    L, d, f, v = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    w = c["torch_dtype"]
    return {
        "embed": ((v, d), w),
        "blocks": {
            "ln1": {"scale": ((L, d), "float32")},
            "attn": {"wq": ((L, d, h * hd), w), "wk": ((L, d, hkv * hd), w),
                     "wv": ((L, d, hkv * hd), w), "wo": ((L, h * hd, d), w)},
            "ln2": {"scale": ((L, d), "float32")},
            "mlp": {"wi": ((L, d, 2 * f), w), "wo": ((L, f, d), w)},
        },
        "final_norm": {"scale": ((d,), "float32")},
        "lm_head": ((d, v), w),
    }


def lm_weights(c: dict, seed: int) -> dict:
    """Normal weights at 1/sqrt(fan_in), norm scales near 1; ``mlp.wi`` is
    the gate projection's columns followed by the up projection's."""
    is_leaf = (lambda x: isinstance(x, tuple) and len(x) == 2
               and isinstance(x[1], str))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        lm_shapes(c), is_leaf=is_leaf)

    def make(key):
        out = []
        for i, (path, (shape, dt)) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = jax.tree_util.keystr(path)
            if "scale" in name:  # norm scales, f32 as the program keeps them
                out.append(1.0 + 0.1 * jax.random.normal(k, shape))
                continue
            fan_in = shape[-1] if name == "['embed']" else shape[-2]
            x = jax.random.normal(k, shape, jnp.dtype(dt))
            out.append(x * jnp.asarray(1.0 / math.sqrt(fan_in), dt))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key_for(seed, 1))

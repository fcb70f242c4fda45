"""Seeded random weights for a Moonlight (DeepSeek-V3 block) configuration,
in the layout ``repro.models.moonlight`` serves: everything but the routed
experts made on the device in one jitted call; the routed experts made on
the device one MoE layer at a time and copied to host memory, where the
deployment keeps them. The run and its reference take the same arrays,
which this module makes and the program only reads (the host arrays are
read-only)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import key_for


def _normal(key, shape, dt, fan_in):
    x = jax.random.normal(key, shape, jnp.dtype(dt))
    return x * jnp.asarray(1.0 / math.sqrt(fan_in), dt)


def resident(c: dict, seed: int) -> dict:
    """Embedding, head, final norm and, per layer, norms, attention and
    either the dense MLP or the router, bias and shared MLP."""
    d, v, dt = c["hidden_size"], c["vocab_size"], c["torch_dtype"]
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    f, e = c["intermediate_size"], c["n_routed_experts"]
    fs = c["n_shared_experts"] * c["moe_intermediate_size"]

    def make(key):
        ks = iter(jax.random.split(key, 16 * c["num_hidden_layers"] + 3))

        def w(shape, fan_in=None):
            return _normal(next(ks), shape, dt, fan_in or shape[0])

        def scale(n):
            return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)

        layers = []
        for li in range(c["num_hidden_layers"]):
            lp = {"ln1": {"scale": scale(d)}, "ln2": {"scale": scale(d)},
                  "attn": {"wq": w((d, nh * (dn + dr))),
                           "wkv_a": w((d, r + dr)),
                           "kv_norm": {"scale": scale(r)},
                           "wkv_b": w((r, nh * (dn + dv))),
                           "wo": w((nh * dv, d))}}
            if li < c["first_k_dense_replace"]:
                lp["mlp"] = {"wi": w((d, 2 * f)), "wo": w((f, d))}
            else:
                lp["moe"] = {"router": w((d, e)),
                             "bias": jnp.zeros((e,), jnp.float32),
                             "shared": {"wi": w((d, 2 * fs)),
                                        "wo": w((fs, d))}}
            layers.append(lp)
        return {"embed": w((v, d), d), "lm_head": w((d, v)),
                "final_norm": {"scale": scale(d)}, "layers": layers}

    return jax.jit(make)(key_for(seed, 2))


def experts(c: dict, seed: int) -> list[dict]:
    """Per MoE layer, ``wi`` [E, D, 2Fe] (gate columns, then up) and ``wo``
    [E, Fe, D], as read-only host arrays."""
    d, e, fe = (c["hidden_size"], c["n_routed_experts"],
                c["moe_intermediate_size"])
    dt = c["torch_dtype"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return (_normal(k1, (e, d, 2 * fe), dt, d),
                _normal(k2, (e, fe, d), dt, fe))

    out = []
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    for m in range(n_moe):
        wi, wo = make(jax.random.fold_in(key_for(seed, 3), m))
        layer = {"wi": np.asarray(wi), "wo": np.asarray(wo)}
        del wi, wo  # the device copies go; the host arrays stay
        for a in layer.values():
            a.flags.writeable = False
        out.append(layer)
    return out

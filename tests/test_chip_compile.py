"""Compile the main path for a described TPU v5e, at real widths, without a
chip: the RoShamBo layers, the four Pallas kernels, the staged-weight
unpack and Moonlight's decode programs. What the chip's compiler refuses (tiling, VMEM, HBM) fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep these compiles in this one file for the same reason."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.accel.roshambo import RoShamBoCNN
from repro.configs.mamba2_780m import config as mamba2_config
from repro.configs.qwen2_5_3b import config as qwen_config

ROSHAMBO = RoShamBoCNN()
FRAMES = 8  # RoShamBo frames per batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_shapes():
    """(spec, input height/width) of each RoShamBo conv layer."""
    hw, out = ROSHAMBO.cfg.input_hw, []
    for spec in ROSHAMBO.cfg.layers:
        out.append((spec, hw))
        hw = hw // 2 if spec.pool else hw
    return out


LAYERS = _layer_shapes()
LAYER_IDS = [spec.name for spec, _ in LAYERS]


@pytest.mark.parametrize("spec,hw", LAYERS, ids=LAYER_IDS)
def test_roshambo_layer_compiles(one_chip, spec, hw):
    def layer(w, b, x):
        return ROSHAMBO.layer_apply(spec, {"w": w, "b": b}, x)
    k = spec.kernel
    jax.jit(layer).lower(
        _sds(one_chip, (k, k, spec.c_in, spec.c_out)),
        _sds(one_chip, (spec.c_out,)),
        _sds(one_chip, (FRAMES, hw, hw, spec.c_in))).compile()


@pytest.mark.parametrize("spec,hw", LAYERS, ids=LAYER_IDS)
def test_conv2d_kernel_compiles(one_chip, spec, hw):
    from repro.kernels.conv2d.ops import conv2d_relu
    k = spec.kernel
    c = jax.jit(conv2d_relu).lower(
        _sds(one_chip, (FRAMES, hw, hw, spec.c_in)),
        _sds(one_chip, (k, k, spec.c_in, spec.c_out)),
        _sds(one_chip, (spec.c_out,))).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode,size", [("blocks", 2048), ("unique", 512)])
def test_streamed_matmul_compiles(one_chip, mode, size):
    from repro.kernels.streamed_matmul.kernel import matmul_blocks, matmul_unique
    fn = matmul_blocks if mode == "blocks" else matmul_unique
    x = _sds(one_chip, (size, size), jnp.bfloat16)
    c = jax.jit(fn).lower(x, x).compile()
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles_at_qwen_heads(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    cfg = qwen_config()
    b, s, dh = 8, 512, cfg.head_dim_
    q = _sds(one_chip, (b, s, cfg.n_heads, dh), jnp.bfloat16)
    kv = _sds(one_chip, (b, s, cfg.n_kv_heads, dh), jnp.bfloat16)
    c = jax.jit(flash_attention).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_call
    cfg = mamba2_config()
    b, s = 2, 4 * cfg.ssm_chunk
    h, p = cfg.n_ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    c = jax.jit(lambda *a: ssd_intra_chunk_call(*a, chunk=cfg.ssm_chunk)
                ).lower(_sds(one_chip, (b, s, h, p), jnp.bfloat16),
                        _sds(one_chip, (b, s, h)),
                        _sds(one_chip, (h,)),
                        _sds(one_chip, (b, s, g, n), jnp.bfloat16),
                        _sds(one_chip, (b, s, g, n), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_qwen_serve_step_fits_one_chip(one_chip, step):
    """The served path's prefill and decode at full qwen2.5-3b width
    (batch 4, prompt 128) compile and fit the chip's 16 GB of HBM."""
    from repro.models.api import build_model
    model = build_model(qwen_config())
    b, s, max_seq = 4, 128, 168

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if step == "prefill":
        c = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, max_seq)
                    ).lower(params, _sds(one_chip, (b, s), jnp.int32))
    else:
        cache = on_chip(jax.eval_shape(lambda: model.init_cache(b, max_seq)))
        c = jax.jit(model.decode, donate_argnums=(2,)).lower(
            params, _sds(one_chip, (b, 1), jnp.int32), cache)
    m = c.compile().memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < 16e9


@pytest.mark.parametrize("program", ["route", "expert", "head"])
def test_moonlight_decode_programs_compile(one_chip, program):
    """A decode step's programs at Moonlight-16B-A3B's published widths (4
    rows over a 4096-position latent cache): a MoE layer's attention and
    router, the largest group of routed experts a decode step applies in
    one program, the head."""
    from repro.configs.moonlight_16b_a3b import config as moonlight_config
    from repro.models import moonlight as ml
    cfg = moonlight_config()
    b, s_max, d, bf = 4, 4096, cfg.d_model, jnp.bfloat16
    x = _sds(one_chip, (b, 1, d))
    if program == "route":
        from repro.models.layers.mla import mla_params
        fs, e = cfg.n_shared_experts * cfg.d_expert, cfg.n_experts
        attn = jax.eval_shape(
            lambda: mla_params(jax.random.PRNGKey(0), cfg, bf))
        lp = {"ln1": {"scale": _sds(one_chip, (d,))},
              "ln2": {"scale": _sds(one_chip, (d,))},
              "attn": jax.tree.map(
                  lambda a: _sds(one_chip, a.shape, a.dtype), attn),
              "moe": {"router": _sds(one_chip, (d, e), bf),
                      "bias": _sds(one_chip, (e,)),
                      "shared": {"wi": _sds(one_chip, (d, 2 * fs), bf),
                                 "wo": _sds(one_chip, (fs, d), bf)}}}
        c = ml.moonlight_route.lower(
            cfg, 0, lp, x, _sds(one_chip, (b, s_max, cfg.kv_lora_rank), bf),
            _sds(one_chip, (b, s_max, cfg.qk_rope_head_dim), bf),
            _sds(one_chip, (b,), jnp.int32))
    elif program == "expert":
        k, fe = cfg.top_k, cfg.d_expert
        group = max(ml.group_sizes(b * k, 3))
        c = ml.moonlight_expert.lower(
            x, _sds(one_chip, (b, 1, d), bf),
            _sds(one_chip, (b, 1, k), jnp.int32), _sds(one_chip, (b, 1, k)),
            (_sds(one_chip, (), jnp.int32),) * group,
            (_sds(one_chip, (d, 2 * fe), bf), _sds(one_chip, (fe, d), bf))
            * group)
    else:
        c = ml.moonlight_head.lower(
            cfg, _sds(one_chip, (d,)), _sds(one_chip, (d, cfg.vocab), bf), x,
            _sds(one_chip, (), jnp.int32))
    m = c.compile().memory_analysis()
    assert m.temp_size_in_bytes < 64e6


@pytest.mark.parametrize("block_bytes", [0, 1 << 20], ids=["unique", "blocks"])
@pytest.mark.parametrize("dtype,shape", [
    (np.float32, (1024, 8192)),
    (jnp.bfloat16, (1024, 16384)),
], ids=["f32", "bf16"])
def test_staged_unpack_temp_bounded(one_chip, dtype, shape, block_bytes):
    """Unpacking one 32 MiB staged weight (the streaming_layers MLP width)
    takes at most twice its bytes of device temporaries."""
    from repro.core.transfer import StagedLayout, _unpack_program
    lay = StagedLayout([np.empty(shape, dtype)])
    nbytes = lay.nbytes
    assert nbytes == 32 << 20
    w = lay.word.itemsize
    per = (block_bytes or nbytes) // w
    chunks = [_sds(one_chip, (per,), lay.word)
              for _ in range(math.ceil(nbytes // w / per))]
    c = _unpack_program(lay.specs, lay.word).lower(chunks).compile()
    assert c.memory_analysis().temp_size_in_bytes <= 2 * nbytes

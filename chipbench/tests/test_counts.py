"""Operations and bytes from shapes, against numbers worked by hand."""

import json
import pathlib

import pytest

from chipbench.harness import counts
from chipbench.harness.peaks import PEAKS, peaks_for, roofline_s

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_roshambo_frame_flops_by_hand():
    # 2*H*W*9*Cin*Cout per conv: 64x64 1->16, 32x32 16->32, 16x16 32->64,
    # 8x8 64->128, 4x4 128->128; FC 4*4*128 -> 4
    convs = [2 * 64 * 64 * 9 * 1 * 16, 2 * 32 * 32 * 9 * 16 * 32,
             2 * 16 * 16 * 9 * 32 * 64, 2 * 8 * 8 * 9 * 64 * 128,
             2 * 4 * 4 * 9 * 128 * 128]
    assert convs == [1179648, 9437184, 9437184, 9437184, 4718592]
    fc = 2 * 2048 * 4
    cfg = _cfg("roshambo")
    assert [l.flops for l in counts.cnn_layers(cfg)] == convs
    assert counts.cnn_frame_flops(cfg) == sum(convs) + fc == 34226176


def test_roshambo_layer_bytes_by_hand():
    conv1 = counts.cnn_layers(_cfg("roshambo"))[0]
    # f32: input 64*64*1, weights 3*3*1*16 + 16 bias, output 32*32*16
    assert conv1.nbytes == 4 * (4096 + 144 + 16 + 16384)
    t, bound = roofline_s(conv1.flops, conv1.nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory"
    assert t == pytest.approx(conv1.nbytes / 819e9)


def test_danube_decode_bytes_at_16_slots_by_hand():
    lm = counts.DenseLM.from_config(_cfg("h2o-danube-1.8b"))
    d, f, v, L = 2560, 6912, 32000, 24
    per_layer = d * 2560 * 2 + 2 * d * 640 + 3 * d * f  # q,o + k,v + mlp
    assert lm.layer_matmul_params == per_layer == 69_468_160
    assert lm.matmul_params == L * per_layer + d * v == 1_749_155_840
    # K and V, 8 heads of 80, 24 layers, bf16
    assert lm.kv_bytes_per_position == 24 * 2 * 8 * 80 * 2 == 61440
    cached = [1000] * 16
    flops, nbytes = lm.decode_step(cached)
    weights = 1_749_155_840 * 2
    kv = 16 * 1000 * 61440 + 16 * 61440
    rows, logits = 16 * 2560 * 2, 16 * 32000 * 4
    assert nbytes == weights + kv + rows + logits == 4_484_464_640
    attn = 24 * 4 * 32 * 80 * 1001
    assert flops == 16 * (2 * 1_749_155_840 + attn)
    t, bound = roofline_s(flops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory" and t == pytest.approx(5.475e-3, rel=1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")

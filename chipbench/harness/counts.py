"""Operations and bytes that the algorithm needs, from shapes alone.

These are what a roofline share and an MFU divide by a measured time. They
count multiply-adds as two operations and bytes moved to and from device
memory once; padding, layout and recomputation that an implementation adds
are not counted."""

from __future__ import annotations

from dataclasses import dataclass


# -- the RoShamBo CNN (conv layers streamed one at a time) ------------------

@dataclass(frozen=True)
class ConvLayer:
    name: str
    hw: int      # input height = width (SAME padding, stride 1)
    c_in: int
    c_out: int
    kernel: int
    pool: bool   # 2x2 max-pool after the ReLU
    itemsize: int

    @property
    def hw_out(self) -> int:
        return self.hw // 2 if self.pool else self.hw

    @property
    def flops(self) -> int:
        return 2 * self.hw * self.hw * self.kernel ** 2 * self.c_in * self.c_out

    @property
    def nbytes(self) -> int:
        """Input fmap + weights + bias read, output fmap written."""
        w = self.kernel ** 2 * self.c_in * self.c_out + self.c_out
        return self.itemsize * (self.hw * self.hw * self.c_in + w
                                + self.hw_out * self.hw_out * self.c_out)


def cnn_layers(cfg: dict, batch: int = 1) -> list[ConvLayer]:
    """The conv layers of a configuration file's ``layers`` list (batch
    folds into the spatial work: every count scales with it)."""
    hw, c_in = cfg["input_hw"], cfg["input_channels"]
    itemsize = {"float32": 4, "bfloat16": 2}[cfg["dtype"]]
    out = []
    for spec in cfg["layers"]:
        lay = ConvLayer(spec["name"], hw, c_in, spec["c_out"],
                        spec["kernel"], spec["pool"], itemsize)
        out.append(lay)
        hw, c_in = lay.hw_out, lay.c_out
    if batch != 1:
        raise ValueError("counts are per frame of batch 1")
    return out


def cnn_fc_flops(cfg: dict) -> int:
    last = cnn_layers(cfg)[-1]
    return 2 * last.hw_out * last.hw_out * last.c_out * cfg["n_classes"]


def cnn_frame_flops(cfg: dict) -> int:
    """Model operations of one frame: every conv and the FC head."""
    return sum(l.flops for l in cnn_layers(cfg)) + cnn_fc_flops(cfg)


# -- a dense decoder LM (GQA attention, gated MLP) ---------------------------

@dataclass(frozen=True)
class DenseLM:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    itemsize: int  # weights and KV cache

    @classmethod
    def from_config(cls, cfg: dict) -> "DenseLM":
        h = cfg["num_attention_heads"]
        return cls(n_layers=cfg["num_hidden_layers"],
                   d_model=cfg["hidden_size"], n_heads=h,
                   n_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or cfg["hidden_size"] // h,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   itemsize={"bfloat16": 2, "float32": 4}[
                       cfg["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + 2 * d * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Weights every token multiplies: the blocks and the LM head (the
        embedding is a lookup)."""
        return self.n_layers * self.layer_matmul_params \
            + self.d_model * self.vocab

    @property
    def kv_bytes_per_position(self) -> int:
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim \
            * self.itemsize

    def token_flops(self, kv_len: int) -> int:
        """One token through the model, attending ``kv_len`` positions
        (itself included)."""
        attn = self.n_layers * 4 * self.n_heads * self.head_dim * kv_len
        return 2 * self.matmul_params + attn

    def decode_step(self, cached: list[int]) -> tuple[int, int]:
        """(operations, bytes) of one decode step over the active slots,
        ``cached[i]`` positions already in slot i's cache. Bytes: every
        weight read once, each active slot's cache read over its valid
        positions, one new key and value written per slot, the embedding
        rows read and the logits written in f32."""
        flops = sum(self.token_flops(n + 1) for n in cached)
        weights = self.matmul_params * self.itemsize
        kv_read = sum(n for n in cached) * self.kv_bytes_per_position
        kv_write = len(cached) * self.kv_bytes_per_position
        rows = len(cached) * self.d_model * self.itemsize
        logits = len(cached) * self.vocab * 4
        return flops, weights + kv_read + kv_write + rows + logits

"""Operations and bytes of a Moonlight (DeepSeek-V3 block) decode step, from
shapes alone, as ``counts.py`` counts them: multiply-adds are two
operations, bytes moved to and from device memory are counted once, and
what an implementation adds (padding, masked work, recomputation) is not
counted."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class MoonlightLM:
    n_layers: int
    n_dense: int
    d_model: int
    n_heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int
    vocab: int
    itemsize: int  # weights and latent cache

    @classmethod
    def from_config(cls, c: dict) -> "MoonlightLM":
        return cls(n_layers=c["num_hidden_layers"],
                   n_dense=c["first_k_dense_replace"],
                   d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   n_experts=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   d_expert=c["moe_intermediate_size"],
                   n_shared=c["n_shared_experts"], vocab=c["vocab_size"],
                   itemsize={"bfloat16": 2, "float32": 4}[c["torch_dtype"]])

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def attn_params(self) -> int:
        d, h, r = self.d_model, self.n_heads, self.kv_rank
        return (d * h * (self.nope + self.rope) + d * (r + self.rope)
                + r * h * (self.nope + self.v_dim) + h * self.v_dim * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert

    @property
    def resident_params(self) -> int:
        """Weights a step reads from device memory whatever the routing:
        attention, the dense MLPs, routers, shared experts and the head
        (the embedding is a lookup of a row a token)."""
        d = self.d_model
        moe = d * self.n_experts + self.n_shared * self.expert_params
        return (self.n_layers * self.attn_params
                + self.n_dense * 3 * d * self.d_ff + self.n_moe * moe
                + d * self.vocab)

    @property
    def latent_bytes_per_position(self) -> int:
        return self.n_layers * (self.kv_rank + self.rope) * self.itemsize

    def token_flops(self, kv_len: int) -> int:
        """Model operations of one token attending ``kv_len`` positions
        (itself included): every projection, attention in the published
        (expanded) form, the router, shared and top-k routed experts."""
        h = self.n_heads
        core = self.n_layers * 2 * h * (self.nope + self.rope
                                         + self.v_dim) * kv_len
        active = (self.n_layers * self.attn_params
                  + self.n_dense * 3 * self.d_model * self.d_ff
                  + self.n_moe * (self.d_model * self.n_experts
                                  + (self.n_shared + self.top_k)
                                  * self.expert_params)
                  + self.d_model * self.vocab)
        return 2 * active + core

    def step_flops(self, cached: list[int]) -> int:
        """Model operations of one decode step, ``cached[i]`` positions in
        row i's cache before it."""
        return sum(self.token_flops(n + 1) for n in cached)

    def decode_step(self, cached: list[int],
                    experts: list[int]) -> tuple[int, int]:
        """(operations, bytes) of one decode step's device programs, with
        ``experts[m]`` distinct routed experts present in MoE layer m.
        Operations: the model's (:meth:`step_flops`). Bytes: the resident
        weights and each present expert read once, each row's valid
        latent cache read and its new entry written, the embedding rows
        read and the logits written in float32."""
        b = len(cached)
        w = (self.resident_params + sum(experts) * self.expert_params) \
            * self.itemsize
        lat = (sum(cached) + b) * self.latent_bytes_per_position
        rows = b * self.d_model * self.itemsize
        logits = b * self.vocab * 4
        return self.step_flops(cached), w + lat + rows + logits

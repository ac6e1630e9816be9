"""Operations and bytes of the model's work, computed from its shapes.

These are the yardstick for every MFU and roofline share the benchmark
reports: model FLOPs count what the arithmetic needs (no padded rows or
columns, no recomputation), bytes count what the live context holds (the
slots' positions, not the padded block table).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple


@dataclass(frozen=True)
class ModelCost:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_matrices: int          # 3 for a gated MLP, 2 for a plain one
    kv_itemsize: int           # bytes per element of the KV pool
    act_itemsize: int          # bytes per element of the activations

    @classmethod
    def from_config(cls, c: dict) -> "ModelCost":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"],
                   mlp_matrices=3 if c["mlp_gated"] else 2,
                   kv_itemsize=_itemsize(c["deployment"]["cache_dtype"]),
                   act_itemsize=_itemsize(c["torch_dtype"]))

    @property
    def layer_matmul_params(self) -> int:
        d, q = self.d_model, self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + self.mlp_matrices * d * self.d_ff

    def dense_flops(self, tokens: float, layers: int = -1) -> float:
        """Matmul FLOPs of ``tokens`` tokens through ``layers`` layers (all
        by default) plus the output head."""
        n = self.n_layers if layers < 0 else layers
        return 2.0 * tokens * (n * self.layer_matmul_params
                               + self.d_model * self.vocab)

    def attn_flops(self, ctx: float) -> float:
        """Score and value FLOPs of one query over ``ctx`` keys, all layers."""
        return 4.0 * ctx * self.n_heads * self.head_dim * self.n_layers

    def prompt_flops(self, plen: int) -> float:
        """Model FLOPs of a prompt of ``plen`` true tokens (causal)."""
        return self.dense_flops(plen) + 2.0 * plen * (plen + 1) * \
            self.n_heads * self.head_dim * self.n_layers

    def token_flops(self, ctx: int) -> float:
        """Model FLOPs of one token that attends ``ctx`` keys."""
        return self.dense_flops(1) + self.attn_flops(ctx)

    def decode_attn(self, ctxs: Iterable[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) the paged decode kernel needs for one step over
        slots whose live contexts are ``ctxs``: every key and value of the
        live context read once per layer, plus the queries and outputs."""
        ctxs = list(ctxs)
        total = float(sum(ctxs))
        kv = total * 2 * self.n_kv_heads * self.head_dim * self.kv_itemsize
        qo = len(ctxs) * 2 * self.n_heads * self.head_dim * self.act_itemsize
        flops = 4.0 * total * self.n_heads * self.head_dim
        return flops * self.n_layers, (kv + qo) * self.n_layers


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]

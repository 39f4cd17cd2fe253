"""Operations and bytes that the algorithm needs, from shapes alone.

These count the work a step must do, whatever implements it: recomputation
is not counted, a decode step reads each live slot's cache at its real
length (never the padded view, never the cache repeated up to the query
heads), and a prefill chunk counts its real width (never its pow2 bucket).
A later program that does less redundant work then shows as a higher share
of the roofline, not as a stale count.

``Shape`` holds a dense decoder's published widths, as a configuration file
under ``bench/configs`` gives them.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    layers: int
    d: int            # hidden size
    heads: int        # query heads
    kv_heads: int
    head_dim: int
    ffn: int          # intermediate size (gated MLP: three matrices)
    vocab: int
    tied: bool = True

    @classmethod
    def from_model(cls, m: dict) -> "Shape":
        return cls(
            layers=m["num_hidden_layers"], d=m["hidden_size"],
            heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
            head_dim=m["head_dim"], ffn=m["intermediate_size"],
            vocab=m["vocab_size"], tied=m["tie_word_embeddings"],
        )

    @property
    def layer_matmul_params(self) -> int:
        s = self
        attn = 2 * s.d * s.heads * s.head_dim + 2 * s.d * s.kv_heads * s.head_dim
        return attn + 3 * s.d * s.ffn

    @property
    def head_params(self) -> int:
        return self.vocab * self.d

    @property
    def params(self) -> int:
        """Every parameter: blocks, norms, embedding (and an untied head)."""
        s = self
        n = s.layers * (s.layer_matmul_params + 2 * s.d) + s.d + s.vocab * s.d
        return n if s.tied else n + s.head_params

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """Key and value of one position in every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * dtype_bytes

    def attn_flops(self, contexts: int) -> int:
        """Forward attention FLOPs (scores and weighted sum) of queries whose
        context lengths sum to ``contexts``, over all layers."""
        return 4 * self.heads * self.head_dim * contexts * self.layers


def causal_contexts(start: int, width: int) -> int:
    """Sum of context lengths of positions start..start+width-1 (each sees
    itself and everything before it)."""
    return width * start + width * (width + 1) // 2


def train_flops_per_token(s: Shape, seq: int) -> float:
    """Forward and backward FLOPs per trained token (3x the forward),
    causal attention counted at its real half, no recomputation."""
    fwd = 2 * (s.layers * s.layer_matmul_params + s.head_params) * seq
    fwd += s.attn_flops(causal_contexts(0, seq))
    return 3.0 * fwd / seq


def prefill_chunk(s: Shape, start: int, width: int, *,
                  weight_bytes: int, kv_dtype_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one prompt chunk of real ``width`` at ``start``.

    The head runs on the chunk's last position only (the program returns
    that logit row).  Bytes: every weight once, the cache of the earlier
    ``start`` positions read, the chunk's own keys and values written.
    """
    flops = 2 * s.layers * s.layer_matmul_params * width + 2 * s.head_params
    flops += s.attn_flops(causal_contexts(start, width))
    kv = s.kv_bytes_per_token(kv_dtype_bytes)
    return float(flops), float(weight_bytes + kv * (start + width))


def decode_step(s: Shape, lengths, *, weight_bytes: int,
                kv_dtype_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the live slots.

    ``lengths``: cached positions of each live slot before the step; its new
    token attends to those and to itself.  Bytes: every weight once, each
    live slot's cache at its real length read, one new position written.
    """
    lengths = list(lengths)
    n = len(lengths)
    flops = 2 * (s.layers * s.layer_matmul_params + s.head_params) * n
    flops += s.attn_flops(sum(l + 1 for l in lengths))
    kv = s.kv_bytes_per_token(kv_dtype_bytes)
    return float(flops), float(weight_bytes + kv * (sum(lengths) + n))

"""Operations that a training step of a latent-attention MoE model needs,
from the configuration's widths and the step's routing counts.

As in ``bench/counts.py``: recomputation is not counted, causal attention
is counted at its real half (each position attends to itself and what
precedes it), and the routed experts are counted at the assignments they
were given, which the program reports per step (``held_assignments``, the
``moe.held_assignments`` counter).  ``m`` is a configuration file under
``bench/configs`` with the model's keys at its top level.
"""
from __future__ import annotations

from bench.counts import causal_contexts

def attn_params(m: dict) -> int:
    """Matrix parameters of one latent-attention layer (no query latent)."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    r, nope, rope = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd = m["v_head_dim"]
    return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) + H * vd * d


def expert_flops_per_assignment(m: dict) -> float:
    """Forward FLOPs of one (token, expert) assignment: the gated MLP."""
    return 2.0 * 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_forward_flops(m: dict, seq: int) -> float:
    """Forward FLOPs of one sequence of ``seq`` tokens, routed experts
    left out: attention projections and scores, the dense layers' FFN, the
    shared experts, the routers and the head."""
    d, V = m["hidden_size"], m["vocab_size"]
    L, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    H = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    moe_layers = L - dense
    per_token = 2.0 * L * attn_params(m)
    per_token += 2.0 * 3 * d * m["intermediate_size"] * dense
    shared = m["n_shared_experts"] * m["moe_intermediate_size"]
    per_token += (2.0 * 3 * d * shared + 2.0 * d * m["experts_routed"]) * moe_layers
    per_token += 2.0 * d * V
    scores = 2.0 * H * (qk + m["v_head_dim"]) * causal_contexts(0, seq) * L
    return per_token * seq + scores


def train_flops_per_token(m: dict, seq: int, held_assignments_per_seq: float) -> float:
    """Forward and backward FLOPs per trained token (3x the forward), the
    routed experts at the assignments held here."""
    fwd = fixed_forward_flops(m, seq)
    fwd += held_assignments_per_seq * expert_flops_per_assignment(m)
    return 3.0 * fwd / seq


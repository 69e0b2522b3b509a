"""Useful work of a training step of a DeepSeek-V3-family model (MLA
without q_lora, dense and expert layers), fixed by the model and the
batch and not by how the program computes it.

Read from a configuration file's numbers (Hugging Face's key names, plus
``router_experts`` for the router's width where ``n_routed_experts``
counts the experts held here)."""

from __future__ import annotations


def matmul_params_per_token(c: dict) -> float:
    """Matmul parameters one token passes through here: MLA's four
    projections in every layer, the dense MLP, and in each expert layer
    the router, the shared experts and the held experts' expected share
    of the k routed ones (k * held / router width); the output head.
    The embedding is a lookup, not a matmul."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    mla = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + dv) \
        + H * dv * D
    dense = c["first_k_dense_replace"]
    moe_layers = c["num_hidden_layers"] - dense
    F = c["moe_intermediate_size"]
    E = c["router_experts"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / E
    moe = D * E + 3 * D * F * (c["n_shared_experts"] + routed)
    return (c["num_hidden_layers"] * mla + dense * 3 * D
            * c["intermediate_size"] + moe_layers * moe
            + D * c["vocab_size"])


def attention_forward_flops(c: dict, seq: int) -> float:
    """One sequence's causal attention through every layer, forward: the
    query-key and probability-value products over the seq (seq + 1) / 2
    causal pairs."""
    H = c["num_attention_heads"]
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return c["num_hidden_layers"] * 2.0 * H * width * seq * (seq + 1) / 2


def train_step_flops(c: dict, seq: int, batch: int) -> float:
    """6 x matmul parameters x tokens, plus 3 x the causal attention
    forward (forward and backward); recomputation does not count."""
    return batch * (6.0 * matmul_params_per_token(c) * seq
                    + 3.0 * attention_forward_flops(c, seq))

"""Operations and bytes the latent-attention, routed-expert decoder needs,
from the configuration's shapes alone (the published key names). Kept with
the benchmark so that no later PR can change the yardstick.

Conventions. A matrix of p parameters costs 2p FLOPs a token that meets
it and p * itemsize bytes a pass that reads it. A token meets
``num_experts_per_tok`` routed experts, not all of them. Attention is
charged as the published equations state it, with keys and values given:
per layer, head and visible position 2 * qk_head_dim FLOPs for the score
and 2 * v_head_dim for its share of the output (the absorbed decode form
does more arithmetic on fewer bytes; that is the program's choice and is
not counted). Norms, RoPE, the softmax and the sampler are left out: they
are no matrix products and two orders smaller.
"""

from __future__ import annotations


def layer_params(c: dict) -> dict:
    """Matrix parameters of one layer, by part."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    r = c["kv_lora_rank"]
    f = c["moe_intermediate_size"]
    return {
        "attention": d * h * qk + d * (r + c["qk_rope_head_dim"])
        + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + h * c["v_head_dim"] * d,
        "dense_ffn": 3 * d * c["intermediate_size"],
        "shared": 3 * d * f * c["n_shared_experts"],
        "router": d * c["n_routed_experts"],
        "expert": 3 * d * f,
    }


def n_expert_layers(c: dict) -> int:
    return max(c["num_hidden_layers"] - c["first_k_dense_replace"], 0)


def num_params(c: dict) -> int:
    """Every parameter of the model as this configuration cuts it: what
    the device holds."""
    p, d = layer_params(c), c["hidden_size"]
    dense, moe = c["num_hidden_layers"] - n_expert_layers(c), n_expert_layers(c)
    norms = c["num_hidden_layers"] * (2 * d + c["kv_lora_rank"]) + d
    return (
        2 * c["vocab_size"] * d + norms
        + c["num_hidden_layers"] * p["attention"] + dense * p["dense_ffn"]
        + moe * (p["shared"] + p["router"] + c["n_routed_experts"]
                 + c["n_routed_experts"] * p["expert"])
    )


def active_params(c: dict, head: bool) -> int:
    """Matrix parameters one token meets, with or without the head (a
    prefilled position needs no logits)."""
    p = layer_params(c)
    dense, moe = c["num_hidden_layers"] - n_expert_layers(c), n_expert_layers(c)
    n = c["num_hidden_layers"] * p["attention"] + dense * p["dense_ffn"]
    n += moe * (p["shared"] + p["router"]
                + c["num_experts_per_tok"] * p["expert"])
    return n + (c["vocab_size"] * c["hidden_size"] if head else 0)


def attention_flops(c: dict, visible: float) -> float:
    """FLOPs of one token's attention over ``visible`` positions, all
    layers."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (c["num_hidden_layers"] * c["num_attention_heads"]
            * 2 * (qk + c["v_head_dim"]) * visible)


def token_flops(c: dict, visible: float, head: bool) -> float:
    return 2 * active_params(c, head) + attention_flops(c, visible)


def latent_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes one position leaves in the caches of all layers."""
    return (c["num_hidden_layers"] * itemsize
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]))


def pass_bytes(c: dict, experts_touched: float, latent_rows: float,
               head: bool, itemsize: int = 2) -> float:
    """Bytes one pass over the model must read: ``experts_touched``
    routed experts in each expert layer (each once), every other weight
    once (the embedding's few rows and the norms are left out), and
    ``latent_rows`` cached positions in every layer."""
    p = layer_params(c)
    dense, moe = c["num_hidden_layers"] - n_expert_layers(c), n_expert_layers(c)
    weights = (
        c["num_hidden_layers"] * p["attention"] + dense * p["dense_ffn"]
        + moe * (p["shared"] + p["router"] + experts_touched * p["expert"])
        + (c["vocab_size"] * c["hidden_size"] if head else 0)
    )
    return itemsize * weights + latent_rows * latent_row_bytes(c, itemsize)

"""What a served step of the window_moe family (sliding-window and full
attention side by side, routed experts held as a share) needs, from the
configuration's shapes (the published key names) and the counters its
driver took over the window. ``readers/served_yardsticks.py`` says what
the three functions are for. A program without the family's counters
gives nothing to count: ``None``.

Conventions. A matrix of p parameters costs 2p FLOPs a token that meets
it and p * 2 bytes a pass that reads it (bfloat16 as served); the
embedding is read by row. A routed expert is met by the assignments the
program HELD (``moe_held_assignments``: the router's others are dropped
on this chip) and read once a step or block by each held expert the
program's counter says it touched. Attention is charged per (query,
visible row) pair: per query head 2 d FLOPs for the score and 2 d for its
share of the output; a sliding layer's query sees ``min(p + 1,
sliding_window)`` rows, a full layer's ``p + 1`` (the driver's
``decode_window_rows_sum`` and ``decode_context_sum``, and their prefill
twins). A decode step reads, per live slot and layer, the K and V rows
its position needs at ``2 x kv heads x head_dim x 2`` bytes a row (4,096
at the published widths): not the whole rings and rows the plain program
reads. Norms, RoPE, the gate's sigmoid, the softmax and the sampler are
left out. A prefill block is charged one query's rows of K and V (a
floor: the union over its queries is larger)."""

from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def attention_params(c: dict) -> int:
    """Matrix parameters of one layer's attention: q, gate, out; k, v."""
    d = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 3 * d * q + 2 * d * kv


def expert_params(c: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_experts(c: dict) -> int:
    return c.get("router_experts") or c["num_experts"]


def n_layers(c: dict, kind: str) -> int:
    return sum(k == kind for k in c["layer_types"])


def n_expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def shared_params(c: dict) -> int:
    """Matrix parameters every token meets: attention, the dense layers'
    SwiGLU, the expert layers' routers and shared experts."""
    d = c["hidden_size"]
    moe = d * router_experts(c) + c["num_shared_experts"] * expert_params(c)
    return (c["num_hidden_layers"] * attention_params(c)
            + c["num_dense_layers"] * 3 * d * c["intermediate_size"]
            + n_expert_layers(c) * moe)


def num_params(c: dict) -> int:
    """Every parameter of the model as this configuration cuts it: what
    the device holds (the model's own tree counts the same)."""
    d, hd = c["hidden_size"], c["head_dim"]
    norms = c["num_hidden_layers"] * (4 * d + 2 * hd) + d
    return (2 * c["vocab_size"] * d + norms + shared_params(c)
            + n_expert_layers(c) * (c["num_experts"] * expert_params(c)
                                    + router_experts(c)))


def weight_bytes(c: dict) -> int:
    """What the device holds of the weights: bfloat16, the router's bias
    float32."""
    bias = n_expert_layers(c) * router_experts(c)
    return 2 * (num_params(c) - bias) + 4 * bias


def attention_flops(c: dict, window_rows: float, context_rows: float) -> float:
    """FLOPs of attention over ``window_rows`` (query, row) pairs of each
    sliding layer and ``context_rows`` of each full layer."""
    pairs = (n_layers(c, SLIDING) * window_rows
             + n_layers(c, FULL) * context_rows)
    return c["num_attention_heads"] * 4 * c["head_dim"] * pairs


def row_bytes(c: dict, itemsize: int = 2) -> int:
    """One position's key and value of one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def _has(k: dict) -> bool:
    return bool(k.get("moe_expert_layer_steps"))


def _flops(c: dict, tokens, held, window_rows, context_rows, head: bool):
    return (2 * tokens * (shared_params(c)
                          + (c["vocab_size"] * c["hidden_size"] if head else 0))
            + 2 * held * expert_params(c)
            + attention_flops(c, window_rows, context_rows))


def _prefill_rows(k: dict) -> tuple:
    """Mean (sliding, full) rows a prefilled position sees, over the
    prompts the driver followed from submission into the window."""
    n = k.get("prefill_context_tokens") or 0
    if not n:
        return 0.0, 0.0
    return k["prefill_window_context_sum"] / n, k["prefill_context_sum"] / n


def window_flops(c: dict, k: dict):
    if not _has(k):
        return None
    win, ctx = _prefill_rows(k)
    n = k["prefill_tokens"]
    return (
        _flops(c, k["decode_tokens"], k["moe_held_assignments"],
               k["decode_window_rows_sum"], k["decode_context_sum"], True)
        + _flops(c, n, k.get("moe_feed_held_assignments", 0), n * win,
                 n * ctx, False)
    )


def decode_need(c: dict, k: dict):
    if not _has(k):
        return None
    steps = k["decode_steps"]
    flops = _flops(c, k["decode_tokens"], k["moe_held_assignments"],
                   k["decode_window_rows_sum"], k["decode_context_sum"],
                   True) / steps
    d, v = c["hidden_size"], c["vocab_size"]
    touched = k["moe_experts_touched"] / steps  # over all expert layers
    nbytes = (
        2 * (shared_params(c) + d * v)            # every other weight once
        + k["decode_tokens"] / steps * d * 2       # the embedding, by row
        + touched * 2 * expert_params(c)           # held experts touched
        + (n_layers(c, SLIDING) * k["decode_window_rows_sum"]
           + n_layers(c, FULL) * k["decode_context_sum"]) / steps * row_bytes(c)
    )
    return flops, nbytes


def prefill_need(c: dict, k: dict):
    blocks = k.get("moe_feed_expert_layer_blocks", 0) / max(n_expert_layers(c), 1)
    if not _has(k) or not blocks:
        return None
    rows = k["prefill_tokens"] / blocks  # positions a block fed
    win, ctx = _prefill_rows(k)
    flops = _flops(c, rows, k["moe_feed_held_assignments"] / blocks,
                   rows * win, rows * ctx, False)
    touched = k["moe_feed_experts_touched"] / blocks
    nbytes = (
        2 * shared_params(c) + rows * c["hidden_size"] * 2
        + touched * 2 * expert_params(c)
        + (n_layers(c, SLIDING) * win + n_layers(c, FULL) * ctx) * row_bytes(c)
    )
    return flops, nbytes

"""What a served step of the linear_sparse family (linear-attention
layers with a recurrent state, block-sparse attention layers with an
index cache) needs, from the configuration's shapes (the published key
names) and the counters its driver took over the window.
``readers/served_yardsticks.py`` says what the three functions are for. A
program without the family's counters gives nothing to count: ``None``.

Conventions. A matrix of p parameters costs 2p FLOPs a token that meets
it and p * 2 bytes a pass that reads it (bfloat16 as served); the
embedding is read by row. A lightning layer's recurrence is charged as
the published equations state it: per token and head 2 d^2 FLOPs for
``k^T v`` into the state and 2 d^2 for ``q S`` (the blocked scan does
other arithmetic on the same state; that is the program's choice), and
per live slot a step its state read and written, float32. A sparse
layer's attention is charged over the rows the program's own counter
says the queries ATTENDED (``sparse_rows_attended``: a key/value group's
rows, the same for every group): per row and query head 2 d FLOPs for
the score and 2 d for its share of the output, and K and V of every group
read once; the pooled keys a query scores are charged by bytes (present
windows = visible rows / stride), their FLOPs — under 0.2% of a token's
— left out, as are norms, RoPE, the softmax and the sampler. A prefill
block is charged one query's rows of K and V (a floor: the union over
its queries is larger and not counted by the program); its time is bound
by FLOPs at 512 rows, so the floor binds nothing.
"""

from __future__ import annotations

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def layer_params(c: dict) -> dict:
    """Matrix parameters of one layer, by part."""
    d = c["hidden_size"]
    lin = c["lightning_nh"] * c["lightning_head_dim"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return {
        LIGHTNING: 5 * d * lin,            # q, k, v, gate, out
        SPARSE: 3 * d * q + 2 * d * kv,    # q, gate, out; k, v
        "mlp": 3 * d * c["intermediate_size"],
    }


def n_layers(c: dict, kind: str) -> int:
    return sum(m == kind for m in c["mixer_types"])


def num_params(c: dict) -> int:
    """Every parameter of the model as this configuration cuts it: what
    the device holds (the model's own tree counts the same)."""
    p, d = layer_params(c), c["hidden_size"]
    norms = (len(c["mixer_types"]) * 2 * d + d
             + n_layers(c, LIGHTNING) * 3 * c["lightning_head_dim"]
             + n_layers(c, SPARSE) * 2 * c["head_dim"])
    return (2 * c["vocab_size"] * d + norms
            + sum(p[m] + p["mlp"] for m in c["mixer_types"]))


def active_params(c: dict, head: bool) -> int:
    """Matrix parameters one token meets, with or without the head."""
    p = layer_params(c)
    n = sum(p[m] + p["mlp"] for m in c["mixer_types"])
    return n + (c["vocab_size"] * c["hidden_size"] if head else 0)


def recurrence_flops(c: dict) -> int:
    """FLOPs of one token's recurrences, all lightning layers."""
    return (n_layers(c, LIGHTNING) * c["lightning_nh"]
            * 4 * c["lightning_head_dim"] ** 2)


def attention_flops(c: dict, rows_attended: float) -> float:
    """FLOPs of attention over ``rows_attended`` (query, row) pairs as
    the program counts them: a key/value group's, every head of it."""
    return c["num_attention_heads"] * 4 * c["head_dim"] * rows_attended


def state_bytes(c: dict) -> int:
    """One slot's recurrent states, all lightning layers, float32."""
    return (n_layers(c, LIGHTNING) * c["lightning_nh"]
            * c["lightning_head_dim"] ** 2 * 4)


def row_bytes(c: dict, itemsize: int = 2) -> int:
    """One cached row (a key, or a value, or a pooled key) of every
    key/value group of one layer."""
    return c["num_key_value_heads"] * c["head_dim"] * itemsize


def _has(k: dict) -> bool:
    return bool(k.get("sparse_layer_steps"))


def window_flops(c: dict, k: dict):
    if not _has(k):
        return None
    return (
        k["decode_tokens"] * (2 * active_params(c, head=True)
                              + recurrence_flops(c))
        + attention_flops(c, k["sparse_rows_attended"])
        + k["prefill_tokens"] * (2 * active_params(c, head=False)
                                 + recurrence_flops(c))
        + attention_flops(c, k.get("sparse_feed_rows_attended", 0))
    )


def decode_need(c: dict, k: dict):
    if not _has(k):
        return None
    steps = k["decode_steps"]
    live = k["decode_tokens"] / steps  # slots a step advanced
    stride = c["sparse_config"]["kernel_stride"]
    flops = (
        k["decode_tokens"] * (2 * active_params(c, head=True)
                              + recurrence_flops(c))
        + attention_flops(c, k["sparse_rows_attended"])
    ) / steps
    nbytes = (
        2 * active_params(c, head=True)              # every weight once
        + live * c["hidden_size"] * 2                # the embedding, by row
        + live * 2 * state_bytes(c)                  # read and written
        + k["sparse_rows_visible"] / steps / stride * row_bytes(c)
        + k["sparse_rows_attended"] / steps * 2 * row_bytes(c)   # K and V
    )
    return flops, nbytes


def prefill_need(c: dict, k: dict):
    n_sparse = n_layers(c, SPARSE)
    blocks = k.get("sparse_feed_layer_blocks", 0) / max(n_sparse, 1)
    if not _has(k) or not blocks:
        return None
    rows = k["prefill_tokens"] / blocks  # positions a block fed
    attended = k["sparse_feed_rows_attended"] / blocks  # (query, row) pairs
    stride = c["sparse_config"]["kernel_stride"]
    flops = (rows * (2 * active_params(c, head=False) + recurrence_flops(c))
             + attention_flops(c, attended))
    per_query = k["sparse_feed_rows_attended"] / max(k["prefill_tokens"], 1)
    visible = k["sparse_feed_rows_visible"] / max(k["prefill_tokens"], 1)
    nbytes = (
        2 * active_params(c, head=False) + rows * c["hidden_size"] * 2
        + 2 * state_bytes(c)
        + visible / stride * row_bytes(c) + per_query * 2 * row_bytes(c)
    )
    return flops, nbytes

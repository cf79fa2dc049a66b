"""What a served ProGen step needs, from the configuration's shapes and
the counters the driver took over the window — whatever the program
reads. ``readers/served_yardsticks.py`` says what the three functions
are for.

Conventions. A matrix of p parameters costs 2p FLOPs a token that meets
it (``flops.train_flops_per_token``'s forward third: every parameter but
the SGU's (n, n) matrices, which are charged by the rows a position
mixes). Bytes are reads: every weight once a pass in the type it is
served in — the leaves Flax converts at use (``kernel``, ``bias``,
``embedding``: ``served_tree.promoted_mask``'s rule) at the compute
type's width, a norm's ``scale`` and the SGU's ``spatial_*`` at the
stored width — except the (n, n) matrices and the embedding table, of
which a position reads its own causal row; per token and layer the K and
V rows its window holds at its position (NOT the ring of 2 x window
rows a slot keeps); per gMLP layer the gate-history rows its position
mixes, at the compute type's width (the gate is computed in it; holding
the history wider is the program's choice). Writes (one K, V and gate
row a token), norms, RoPE, the softmax and the sampler are left out.
A prefill block reads the rows its mean position sees, once: no more
than the block's last position needs, so the count stays a least.
"""

from __future__ import annotations

from benchmark import flops

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def n_gmlp(c: dict) -> int:
    return min(c["global_mlp_depth"], c["depth"])


def kept_params(c: dict) -> int:
    """Parameters no Flax module converts at use: the ScaleNorm scales
    (two a layer, the final one, one inside each SGU) and the SGU's
    spatial weights and biases."""
    half, n = c["ff_mult"] * c["dim"] // 2, c["seq_len"]
    return c["dim"] * (2 * c["depth"] + 1) + n_gmlp(c) * (half + n * n + n)


def widths(c: dict) -> tuple:
    """(bytes of a served converted leaf's element, of a kept one's)."""
    stored, compute = _ITEMSIZE[c["param_dtype"]], _ITEMSIZE[c["dtype"]]
    return min(stored, compute), stored


def weight_bytes(c: dict) -> int:
    """Bytes of the tree the engine serves (``served_weight_bytes``)."""
    served, stored = widths(c)
    kept = kept_params(c)
    return (flops.num_params(c) - kept) * served + kept * stored


def window_rows(c: dict, position: int) -> int:
    """K (or V) rows the query at ``position`` sees: its own window up
    to itself and the whole window before it (window 0 has none: its
    phantom keys are arithmetic, not rows)."""
    w = c["window_size"]
    return position % w + 1 + (w if position >= w else 0)


def kv_row_bytes(c: dict) -> int:
    """Bytes of one position's K and V rows over all layers."""
    return c["depth"] * 2 * c["heads"] * c["dim_head"] * widths(c)[0]


def _flops(c: dict, tokens: float, window_rows_sum: float,
           context_sum: float) -> float:
    """FLOPs ``tokens`` positions need that see ``window_rows_sum`` K/V
    rows and mix ``context_sum`` gate rows between them."""
    n, half = c["seq_len"], c["ff_mult"] * c["dim"] // 2
    met = flops.num_params(c) - n_gmlp(c) * n * n
    return (
        tokens * 2 * met
        + c["depth"] * c["heads"] * 4 * c["dim_head"] * window_rows_sum
        + n_gmlp(c) * 2 * half * context_sum
    )


def _bytes(c: dict, tokens: float, window_rows_sum: float,
           context_sum: float) -> float:
    """Bytes one pass must read that feeds ``tokens`` positions."""
    served, stored = widths(c)
    n, half = c["seq_len"], c["ff_mult"] * c["dim"] // 2
    whole = n_gmlp(c) * n * n * stored + c["num_tokens"] * c["dim"] * served
    return (
        weight_bytes(c) - whole
        + tokens * c["dim"] * served             # embedding rows
        + n_gmlp(c) * context_sum * stored       # causal rows of (n, n)
        + window_rows_sum * kv_row_bytes(c)
        + n_gmlp(c) * context_sum * half * served  # gate history
    )


def _counted(k: dict, *names) -> bool:
    return all(k.get(n) is not None for n in names) and bool(k.get(names[0]))


def window_flops(c: dict, k: dict):
    if not _counted(k, "decode_steps", "decode_window_rows_sum",
                    "prefill_window_rows_sum"):
        return None
    return (
        _flops(c, k["decode_tokens"], k["decode_window_rows_sum"],
               k["decode_context_sum"])
        + _flops(c, k["prefill_tokens"], k["prefill_window_rows_sum"],
                 k["prefill_context_sum"])
    )


def decode_need(c: dict, k: dict):
    if not _counted(k, "decode_steps", "decode_window_rows_sum"):
        return None
    steps = k["decode_steps"]
    per_step = (k["decode_tokens"] / steps,
                k["decode_window_rows_sum"] / steps,
                k["decode_context_sum"] / steps)
    return _flops(c, *per_step), _bytes(c, *per_step)


def prefill_need(c: dict, k: dict):
    """One chunk program: the cell's chunks each lie inside one block of
    the engine's prefill width, so a block is a pass over the weights."""
    if not _counted(k, "prefill_blocks", "prefill_window_rows_sum"):
        return None
    blocks, tokens = k["prefill_blocks"], max(k["prefill_tokens"], 1)
    need_flops = _flops(c, tokens, k["prefill_window_rows_sum"],
                        k["prefill_context_sum"]) / blocks
    need_bytes = _bytes(c, tokens / blocks,
                        k["prefill_window_rows_sum"] / tokens,
                        k["prefill_context_sum"] / tokens)
    return need_flops, need_bytes

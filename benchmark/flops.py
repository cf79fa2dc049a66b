"""Operations and bytes the algorithm needs, from shapes alone.

``train_flops_per_token`` is a copy of ``progen_tpu.profiling
.flops_per_token`` (PaLM convention: 6 x parameters for the dense maths,
plus attention, recomputation not counted), kept here so that no later PR
can change the yardstick; PERF.md lists the original for deletion.
"""

from __future__ import annotations


def num_params(c: dict) -> int:
    d, h = c["dim"], c["ff_mult"] * c["dim"]
    inner = c["heads"] * c["dim_head"]
    n = c["num_tokens"] * d
    for i in range(c["depth"]):
        gmlp = (c["depth"] - i) <= c["global_mlp_depth"]
        n += d + d * 3 * inner + inner * d + d  # norm, qkv, out (+bias)
        hidden = h if gmlp else 2 * h  # GLU doubles the in-projection
        n += d + d * hidden + hidden
        half = hidden // 2
        if gmlp:
            n += half + c["seq_len"] ** 2 + c["seq_len"]
            n += half * half + half
        n += half * d + d
    return n + d + d * c["num_tokens"] + c["num_tokens"]


def train_flops_per_token(c: dict) -> int:
    """Forward + backward FLOPs per trained token. The SGU's (n, n) matrix
    does 2*n*d_half forward FLOPs per token, not 2*n*n, so it is charged
    apart from the 6 x parameters rule."""
    n, d_half = c["seq_len"], c["ff_mult"] * c["dim"] // 2
    n_gmlp = min(c["global_mlp_depth"], c["depth"])
    return (
        6 * (num_params(c) - n_gmlp * n * n)
        + n_gmlp * 6 * n * d_half
        + 12 * c["depth"] * c["heads"] * c["dim_head"] * 2 * c["window_size"]
    )


def local_attention_ops_bytes(bh: int, n: int, d: int, w: int,
                              itemsize: int = 2) -> dict:
    """One forward and one backward call of windowed causal attention over
    (bh, n, d) queries. A query at offset i of its window sees the whole
    previous window and i + 1 keys of its own: w + i + 1 pairs, so a window
    has w*w + w*(w+1)/2 (query, key) pairs; the first window's previous
    window is all zeros but the kernel still multiplies it, and so does the
    reference, so it is counted. Per pair: forward QK^T and PV, 4*d FLOPs;
    backward dV, dP, dQ, dK, 8*d FLOPs (the recomputed scores are not
    counted). Bytes: each operand and result crosses HBM once — forward
    reads q, k, v and writes o; backward reads q, k, v, do and writes dq,
    dk, dv."""
    pairs = bh * (n // w) * (w * w + w * (w + 1) // 2)
    tensor = bh * n * d * itemsize
    return {
        "fwd_flops": 4 * d * pairs,
        "bwd_flops": 8 * d * pairs,
        "fwd_bytes": 4 * tensor,
        "bwd_bytes": 7 * tensor,
    }


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds the chip could take, which peak binds)."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

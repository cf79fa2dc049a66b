"""The ops-and-bytes functions against hand counts, and the FLOPs copy
against the program's own arithmetic."""

import json
from pathlib import Path

import pytest

from benchmark import flops

CONFIGS = Path(__file__).resolve().parents[2] / "benchmark" / "configs"


def test_attention_pairs_flops_and_bytes_by_hand():
    # one head, two windows of 4, head size 8: a query at offset i sees the
    # 4 keys of the previous window and i + 1 of its own
    pairs = 2 * sum(4 + i + 1 for i in range(4))
    assert pairs == 52
    cost = flops.local_attention_ops_bytes(bh=1, n=8, d=8, w=4, itemsize=2)
    assert cost["fwd_flops"] == 52 * 4 * 8     # QK^T and PV, 2*d each
    assert cost["bwd_flops"] == 52 * 8 * 8     # dV, dP, dQ, dK
    assert cost["fwd_bytes"] == 4 * (8 * 8 * 2)  # q, k, v in, o out
    assert cost["bwd_bytes"] == 7 * (8 * 8 * 2)  # q, k, v, do in; dq, dk, dv out


def test_attention_cost_scales_with_heads_and_windows():
    one = flops.local_attention_ops_bytes(bh=1, n=1024, d=64, w=512)
    many = flops.local_attention_ops_bytes(bh=128, n=8192, d=64, w=512)
    assert many["fwd_flops"] == one["fwd_flops"] * 128 * 8
    assert many["bwd_bytes"] == one["bwd_bytes"] * 128 * 8


def test_long8k_attention_is_compute_bound_on_a_v5e():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    c = flops.local_attention_ops_bytes(bh=128, n=8192, d=64, w=512)
    t, bound = flops.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], peak)
    assert bound == "compute" and t == c["fwd_flops"] / 197e12
    assert flops.roofline_seconds(1e6, 1e9, peak) == (1e9 / 819e9, "memory")


@pytest.mark.parametrize("name,params", [("large", 1223815168), ("long8k", 183853824)])
def test_the_copy_agrees_with_the_programs_flops_per_token(name, params):
    from progen_tpu.config import ProGenConfig
    from progen_tpu.profiling import flops_per_token

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    config = ProGenConfig.from_dict(cfg)
    assert flops.num_params(cfg) == config.num_params() == params
    assert flops.train_flops_per_token(cfg) == flops_per_token(config)


def test_flops_per_token_by_hand_on_a_one_layer_model():
    c = dict(num_tokens=256, dim=8, depth=1, heads=2, dim_head=4,
             window_size=4, seq_len=8, global_mlp_depth=0, ff_mult=4)
    # embed 2048; attn 8 + 8*24 + 8*8 + 8; ff 8 + 8*64 + 64 + 32*8 + 8;
    # final norm 8 + head 8*256 + 256
    n = 2048 + (8 + 192 + 64 + 8) + (8 + 512 + 64 + 256 + 8) + (8 + 2048 + 256)
    assert flops.num_params(c) == n
    assert flops.train_flops_per_token(c) == 6 * n + 12 * 1 * 2 * 4 * 8

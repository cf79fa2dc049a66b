"""What a served step of the latent-attention, routed-expert family
needs, from the configuration (benchmark/flops_latent_moe.py) and the
counters its driver took over the window: the routed experts the steps
touched (the program's counter) at their bytes, every other weight once,
the live slots' latent rows. ``readers/served_yardsticks.py`` says what
the three functions are for. A program without the family's counters
gives nothing to count: ``None``."""

from benchmark import flops_latent_moe as lm


def window_flops(c: dict, k: dict):
    if not k.get("moe_expert_layer_steps"):
        return None
    return (
        k["decode_tokens"] * 2 * lm.active_params(c, head=True)
        + lm.attention_flops(c, k["decode_context_sum"])
        + k["prefill_tokens"] * 2 * lm.active_params(c, head=False)
        + lm.attention_flops(c, k["prefill_context_sum"])
    )


def decode_need(c: dict, k: dict):
    if not k.get("moe_expert_layer_steps"):
        return None
    steps = k["decode_steps"]
    touched = k["moe_experts_touched"] / k["moe_expert_layer_steps"]
    rows = k["decode_context_sum"] / steps
    need_flops = (k["decode_tokens"] * 2 * lm.active_params(c, head=True)
                  + lm.attention_flops(c, k["decode_context_sum"])) / steps
    return need_flops, lm.pass_bytes(c, touched, rows, head=True)


def prefill_need(c: dict, k: dict):
    blocks = k.get("moe_feed_expert_layer_blocks", 0) / max(
        lm.n_expert_layers(c), 1)
    if not k.get("moe_expert_layer_steps") or not blocks:
        return None
    touched = k["moe_feed_experts_touched"] / k["moe_feed_expert_layer_blocks"]
    rows = k["prefill_tokens"] / blocks  # positions a block fed
    seen = k["prefill_context_sum"] / max(k["prefill_tokens"], 1)
    return (rows * lm.token_flops(c, seen, head=False),
            lm.pass_bytes(c, touched, seen, head=False))

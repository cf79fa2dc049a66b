"""Plain float32 reference of the latent-attention, routed-expert decoder
(the ``deepseek_v3`` model type as its public configs and code describe
it), independent of ``progen_tpu``: the equations as published, no cache,
no absorbed products, no grouping.

* ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, RMSNorm with
  a scale, no biases, an untied head.
* Attention: ``q = W_q u`` -> per head ``[q_nope | q_rope]``;
  ``[c_kv | k_rope] = W_kva u``; ``c = RMSNorm(c_kv)``; per head
  ``[k_nope | v] = W_kvb c``; RoPE on every head's ``q_rope`` and on the
  one ``k_rope`` all heads share (``rope_interleave``: pairs (2i, 2i+1)
  go to the half-split layout first, as the published code does);
  ``k = [k_nope | k_rope]``; causal softmax of ``q . k / sqrt(d_qk)``;
  ``o = W_o concat_h(P v)``.
* Feed-forward: a SwiGLU in the first ``first_k_dense_replace`` layers;
  after them ``s = sigmoid(W_r u)``, the top-k of ``s + bias`` chosen,
  weighted by their own ``s`` normalised to sum 1 and scaled, EVERY
  expert applied to every token and masked by those weights, plus the
  shared SwiGLU.

Weights are upcast from what the tree holds (bfloat16 as served) one
layer, one group of experts and one slice of the vocabulary at a time: a
float32 copy of the whole cut would not fit beside the system under test.
Everything runs under ``default_matmul_precision("highest")`` — on a TPU a
float32 matmul is otherwise computed in bf16 passes. ``compute_dtype``
other than float32 and ``weight_bits`` ((exponent bits, mantissa bits):
every matrix but the router's rounded to that float format first, by
``lax.reduce_precision``, which a TPU without the type still honours)
exist to show what a lower precision would read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _up(tree, dtype, weight_bits=None):
    def one(a):
        if weight_bits is not None and a.ndim >= 2:
            a = jax.lax.reduce_precision(a.astype(jnp.float32), *weight_bits)
        return a.astype(dtype)

    return jax.tree.map(one, tree)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, interleave):
    """x (n, h, r)."""
    r = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = 1.0 / (1.0 * theta) ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, w_gate_up, w_down):
    f = w_down.shape[-2]
    h = u @ w_gate_up
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_down


@functools.partial(jax.jit, static_argnames=("cfg", "dtype", "weight_bits"))
def _attention(p, scale, x, cfg, dtype, weight_bits):
    cfg = dict(cfg)
    p, scale = _up(p, dtype, weight_bits), scale.astype(dtype)
    n = x.shape[0]
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    inter = cfg.get("rope_interleave", True)
    pos = jnp.arange(n)
    u = _norm(x, scale, eps)
    q = (u @ p["w_q"]).reshape(n, h, dn + dr)
    kva = u @ p["w_kva"]
    c = _norm(kva[:, :r], p["kv_norm"], eps)
    kv = (c @ p["w_kvb"]).reshape(n, h, dn + dv)
    k_rope = _rope(kva[:, None, r:], pos, theta, inter)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta, inter)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (n, h, dr))], -1
    )
    scores = jnp.einsum("ihd,jhd->hij", q, k) / (dn + dr) ** 0.5
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    o = jnp.einsum("hij,jhd->ihd", att, kv[..., dn:]).reshape(n, h * dv)
    return x + o @ p["w_o"]


@functools.partial(jax.jit, static_argnames=("dtype", "weight_bits"))
def _dense_ffn(p, u, dtype, weight_bits):
    p = _up(p, dtype, weight_bits)
    return _swiglu(u, p["w_gate_up"], p["w_down"])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _route(w_router, bias, u, given, cfg):
    """Always float32, as published. ``given`` (n, k) names the experts
    to use where it is not negative (see ``forward``). Returns (experts
    (n, k), dense weights (n, E), slack (n,): how far below this
    function's own k-th best score the worst used expert stands; zero
    where the choice is its own)."""
    cfg = dict(cfg)
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ w_router.astype(jnp.float32))
    score = s + bias.astype(jnp.float32)
    best, own = jax.lax.top_k(score, cfg["num_experts_per_tok"])
    idx = jnp.where(given >= 0, given, own)
    slack = best[:, -1] - jnp.take_along_axis(score, idx, axis=-1).min(-1)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(w)
    return idx, dense, slack


@functools.partial(jax.jit, static_argnames=("dtype", "weight_bits"))
def _experts(w_gate_up, w_down, u, weights, dtype, weight_bits):
    """Every expert of this group on every token, masked by the weights."""
    w_gate_up, w_down = _up((w_gate_up, w_down), dtype, weight_bits)
    y = jax.vmap(lambda a, b: _swiglu(u, a, b))(w_gate_up, w_down)  # (e, n, d)
    return jnp.einsum("ne,end->nd", weights.astype(dtype), y)


@functools.partial(jax.jit, static_argnames=("dtype", "weight_bits"))
def _head(w, x, dtype, weight_bits):
    return (x @ _up(w, dtype, weight_bits)).astype(jnp.float32)


def forward(params, tokens, config, *, experts=None,
            compute_dtype=jnp.float32, weight_bits=None,
            expert_group: int = 16, vocab_slices: int = 8,
            return_routing: bool = False):
    """tokens (n,) -> float32 logits (n, vocab); with ``return_routing``
    also {"experts": the experts used, (n, k) per expert layer, "slack":
    per expert layer (n,)}.

    ``experts``, per expert layer an (n, k) array, hands the reference the
    system's DECISIONS where an entry is not negative. Among 128 scores a
    rounding of the router's input moves the k-th and (k+1)-th best past
    each other on a good share of tokens, and one exchanged expert moves
    that token's logits by more than all rounding together; neither
    choice is wrong. So the arithmetic is compared at equal decisions,
    and the decisions are judged by ``slack``: a used expert may stand
    below this reference's own k-th best score only by what rounding
    explains. The weights of the used experts are always the reference's
    own."""
    cfg = tuple(sorted(
        (k, v) for k, v in dict(config).items()
        if isinstance(v, (int, float, bool, str)) or v is None
    ))
    c, dt, wa = dict(cfg), compute_dtype, weight_bits
    eps = c["rms_norm_eps"]
    routing = {"experts": [], "slack": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(dt)
        for i in range(c["num_hidden_layers"]):
            x = _attention(params[f"attn{i}"], params[f"attn_norm{i}"], x,
                           cfg, dt, wa)
            u = _norm(x, params[f"ffn_norm{i}"].astype(dt), eps)
            p = params[f"ffn{i}"]
            if i < c["first_k_dense_replace"]:
                x = x + _dense_ffn(p, u, dt, wa)
                continue
            layer = len(routing["experts"])
            given = (jnp.asarray(experts[layer], jnp.int32) if experts
                     else jnp.full((x.shape[0], c["num_experts_per_tok"]), -1))
            idx, dense, slack = _route(
                p["w_router"], p["e_score_correction_bias"], u, given, cfg
            )
            routing["experts"].append(idx)
            routing["slack"].append(slack)
            y = _dense_ffn(p["shared"], u, dt, wa)
            for lo in range(0, c["n_routed_experts"], expert_group):
                hi = lo + expert_group
                y = y + _experts(p["w_gate_up"][lo:hi], p["w_down"][lo:hi],
                                 u, dense[:, lo:hi], dt, wa)
            x = x + y
        x = _norm(x, params["final_norm"].astype(dt), eps)
        v = c["vocab_size"]
        step = -(-v // vocab_slices)
        logits = jnp.concatenate(
            [_head(params["w_head"][:, lo:lo + step], x, dt, wa)
             for lo in range(0, v, step)], axis=-1,
        )
    return (logits, routing) if return_routing else logits

"""Plain float32 reference of the sliding-window / full-attention decoder
with routed experts held as a share (the ``afmoe`` model type as its
public config and modeling code describe it), independent of
``progen_tpu``: the equations as published, no cache, no ring, no
grouping.

* ``x0 = E[tok] * sqrt(hidden_size)`` (``mup_enabled``).
* ``h = x + N(Attn(N(x)))``, ``x' = h + N(FFN(N(h)))``: four RMSNorms a
  layer, each with a scale, no biases, an untied head
  ``logits = W_head N(x)``.
* Attention: ``q = W_q u``, ``k = W_k u``, ``v = W_v u``, ``g = W_g u``;
  ``q``, ``k`` RMS-normed per head; RoPE (theta ``rope_theta``, the
  half-split layout) on ``sliding_attention`` layers, none on
  ``full_attention`` layers; ``num_attention_heads / num_key_value_heads``
  query heads share a key/value head; softmax of ``q . k / sqrt(d)`` over
  the keys ``s <= p``, and on a sliding layer only those with
  ``p - sliding_window < s``: DENSE scores under that mask;
  ``y = W_o (o * sigmoid(g))``.
* Feed-forward: a SwiGLU in the first ``num_dense_layers`` layers; after
  them ``s = sigmoid(W_r u)`` over the ROUTER's experts
  (``router_experts``, the published ``num_experts``), the top-k of
  ``s + expert_bias``, weighted by their own ``s`` normalised to sum 1 and
  scaled by ``route_scale``, EVERY held expert applied to every token and
  masked by those weights, plus the shared SwiGLU.

Departures from the published description, each a choice of what is
compared, not of arithmetic:
* A layer holds the experts ``[first_expert, first_expert + num_experts)``
  of the router's: an assignment to any other is DROPPED (its weight
  counts in the normalisation and its product is not added), as one chip
  of a deployment that spreads its experts over chips would compute it
  before any exchange.
* The vocabulary is the configuration's slice: ids and logits
  ``[0, vocab_size)``.
* Weights are random, with the norms' scales the caller's (the cell's
  driver sets them) whatever the published initialisation's depth
  scaling.

Weights are upcast from what the tree holds (bfloat16 as served) one
layer, a few experts and a slice of the vocabulary at a time, and queries
attend in blocks: a float32 copy of the whole cut, or of one layer's
scores, would not fit beside the system under test. Everything runs under
``default_matmul_precision("highest")`` — on a TPU a float32 matmul is
otherwise computed in bf16 passes. ``weight_bits`` ((exponent bits,
mantissa bits): every matrix but the router's rounded to that float
format first, by ``lax.reduce_precision``, which a TPU without the type
still honours) exists to show what a lower precision would read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"
# what is upcast or scored at once: two experts (0.35 GB of float32
# activations over 7,200 tokens), a quarter of the vocabulary, 128 queries
# against every key (0.18 GB of scores over 48 heads)
EXPERT_GROUP, VOCAB_SLICES, QUERY_BLOCK = 2, 4, 128


def _up(tree, weight_bits=None):
    def one(a):
        a = a.astype(jnp.float32)
        if weight_bits is not None and a.ndim >= 2:
            a = jax.lax.reduce_precision(a, *weight_bits)
        return a

    return jax.tree.map(one, tree)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (n, h, r), half-split."""
    r = x.shape[-1]
    inv = 1.0 / (1.0 * theta) ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, w_gate_up, w_down):
    f = w_down.shape[-2]
    h = u @ w_gate_up
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_down


@functools.partial(jax.jit,
                   static_argnames=("cfg", "kind", "weight_bits", "block"))
def _attention(p, x, scale, cfg, kind, weight_bits, block):
    """The attention output ``W_o (o * sigmoid(g))`` of every position."""
    cfg = dict(cfg)
    p = _up(p, weight_bits)
    n = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(n)
    u = _norm(x, scale, eps)
    q = _norm((u @ p["w_q"]).reshape(n, h, d), p["q_norm"], eps)
    k = _norm((u @ p["w_k"]).reshape(n, g, d), p["k_norm"], eps)
    v = (u @ p["w_v"]).reshape(n, g, d)
    if kind == SLIDING:
        q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    pad = -n % block
    qb = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, block, h, d)
    pb = jnp.pad(pos, [(0, pad)]).reshape(-1, block)

    def one(args):  # a block of queries against every key
        qi, pi = args
        s = jnp.einsum("ihd,jhd->hij", qi, k) / d ** 0.5
        seen = pi[:, None] >= pos[None, :]
        if kind == SLIDING:
            seen = seen & (pi[:, None] - pos[None, :] < cfg["sliding_window"])
        att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", att, v)

    o = jax.lax.map(one, (qb, pb)).reshape(-1, h * d)[:n]
    return (o * jax.nn.sigmoid(u @ p["w_g"])) @ p["w_o"]


@functools.partial(jax.jit, static_argnames=("weight_bits",))
def _dense_ffn(p, u, weight_bits):
    p = _up(p, weight_bits)
    return _swiglu(u, p["w_gate_up"], p["w_down"])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _route(w_router, bias, u, given, cfg):
    """Always float32, as published. ``given`` (n, k) names the experts
    to use where it is not negative (see ``forward``). Returns (experts
    (n, k), dense weights over the router's experts (n, E), slack (n,):
    how far below this function's own k-th best score the worst used
    expert stands; zero where the choice is its own)."""
    cfg = dict(cfg)
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ w_router.astype(jnp.float32))
    score = s + bias.astype(jnp.float32)
    best, own = jax.lax.top_k(score, cfg["num_experts_per_tok"])
    idx = jnp.where(given >= 0, given, own)
    slack = best[:, -1] - jnp.take_along_axis(score, idx, axis=-1).min(-1)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(w)
    return idx, dense, slack


@functools.partial(jax.jit, static_argnames=("weight_bits",))
def _experts(w_gate_up, w_down, u, weights, weight_bits):
    """Every expert of this group on every token, masked by the weights."""
    w_gate_up, w_down = _up((w_gate_up, w_down), weight_bits)
    y = jax.vmap(lambda a, b: _swiglu(u, a, b))(w_gate_up, w_down)  # (e, n, d)
    return jnp.einsum("ne,end->nd", weights, y)


@functools.partial(jax.jit, static_argnames=("weight_bits",))
def _head(w, x, weight_bits):
    return x @ _up(w, weight_bits)


def forward(params, tokens, config, *, experts=None, weight_bits=None,
            logits_from: int = 0, attention_of=(), return_aux: bool = False):
    """tokens (n,) -> float32 logits (n - logits_from, vocab) of the
    positions from ``logits_from`` on; with ``return_aux`` also
    {"experts": the experts used, (n, k) per expert layer, "slack": per
    expert layer (n,), "attention": {layer: its attention output (n -
    logits_from, hidden)} for the layers in ``attention_of``}.

    ``experts``, per expert layer an (n, k) array of the ROUTER's expert
    ids, hands the reference the system's DECISIONS where an entry is not
    negative. Among 256 scores a rounding of the router's input moves the
    k-th and (k+1)-th best past each other on a share of tokens, and one
    exchanged expert moves that token's logits by more than all rounding
    together; neither choice is wrong. So the arithmetic is compared at
    equal decisions, and the decisions are judged by ``slack``: a used
    expert may stand below this reference's own k-th best score only by
    what rounding explains. The weights of the used experts are always
    the reference's own."""
    cfg = tuple(sorted(
        (k, v) for k, v in dict(config).items()
        if isinstance(v, (int, float, bool, str)) or v is None
    ))
    c = dict(cfg)
    eps, wb = c["rms_norm_eps"], weight_bits
    first, held = c.get("first_expert", 0), c["num_experts"]
    router = c.get("router_experts") or held
    aux = {"experts": [], "slack": [], "attention": {}}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(jnp.float32)
        if c.get("mup_enabled", True):
            x = x * c["hidden_size"] ** 0.5
        for i, kind in enumerate(config["layer_types"]):
            def scale(name):
                return params[f"{name}{i}"].astype(jnp.float32)

            y = _attention(params[f"attn{i}"], x, scale("input_norm"), cfg,
                           kind, wb, QUERY_BLOCK)
            if i in attention_of:
                aux["attention"][i] = y[logits_from:]
            x = x + _norm(y, scale("post_attn_norm"), eps)
            u = _norm(x, scale("pre_mlp_norm"), eps)
            p = params[f"ffn{i}"]
            if i < c["num_dense_layers"]:
                y = _dense_ffn(p, u, wb)
            else:
                layer = len(aux["experts"])
                given = (jnp.asarray(experts[layer], jnp.int32) if experts
                         else jnp.full((x.shape[0], c["num_experts_per_tok"]), -1))
                idx, dense, slack = _route(p["w_router"], p["expert_bias"], u,
                                           given, cfg)
                assert dense.shape[1] == router
                aux["experts"].append(idx)
                aux["slack"].append(slack)
                y = _dense_ffn(p["shared"], u, wb)
                for lo in range(0, held, EXPERT_GROUP):
                    hi = min(lo + EXPERT_GROUP, held)
                    y = y + _experts(p["w_gate_up"][lo:hi], p["w_down"][lo:hi],
                                     u, dense[:, first + lo:first + hi], wb)
            x = x + _norm(y, scale("post_mlp_norm"), eps)
        x = _norm(x[logits_from:], params["final_norm"].astype(jnp.float32), eps)
        v = c["vocab_size"]
        step = -(-v // VOCAB_SLICES)
        logits = jnp.concatenate(
            [_head(params["w_head"][:, lo:lo + step], x, wb)
             for lo in range(0, v, step)], axis=-1,
        )
    return (logits, aux) if return_aux else logits

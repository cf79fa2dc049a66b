"""Plain float32 reference of the hybrid decoder the ``minicpm_sala`` model
type describes (``mixer_types``: ``lightning-attn`` linear-attention
layers and ``minicpm4`` block-sparse attention layers), independent of
``progen_tpu``: the equations of ISSUE 33 section A, no cache, no blocked
scan, no gather.

Block ``l`` (published index; ``L`` = ``total_layers``, 32 whatever the
cut), ``r = scale_depth / sqrt(L)``: ``h <- h + r Mixer_l(RMSNorm(h))``,
``h <- h + r W_down(silu(W_gate u) * W_up u)``, ``u = RMSNorm(h)``;
``h_0 = scale_emb Embed(token)``; ``logits = W_head (RMSNorm(h) /
(hidden_size / dim_model_base))``. No biases.

* ``lightning-attn`` (H heads of d): ``q, k, v = W u``; ``q, k`` RMS-normed
  per head, then RoPE over the whole head (half-split, theta 10,000);
  ``S_t = lam S_(t-1) + k_t^T v_t`` in float32 from ``S = 0``, TOKEN BY
  TOKEN (``lax.scan``); ``o_t = q_t S_t / sqrt(d)``, RMS-normed per head;
  ``y = W_o (o * sigmoid(W_g u))``. ``lam_(l,h) = exp(-s_h (1 - l/(L-1) +
  1e-5))``, ``s_h = 2^(-8(h+1)/H)``.
* ``minicpm4`` (H query heads over G key/value heads, no RoPE): ``q, k``
  RMS-normed per head. Pooled keys ``c_g[j] = mean(k_g[stride j ... stride
  j + kernel - 1])``, present for a query at ``t`` once ``stride j +
  kernel - 1 <= t``. A query at ``t < dense_len`` attends every ``s <=
  t``. From ``dense_len`` on: ``p_h = softmax_j(q_h . c_g[j] / sqrt(d))``
  over present windows, ``a_g = sum_(h in g) p_h``, a block of
  ``block_size`` rows scores the largest ``a_g[j]`` among the windows
  that overlap it; chosen are the ``init_blocks`` first blocks, the
  ``window_size / block_size`` newest up to and holding ``t``, and the
  best-scoring others up to ``topk`` in all, one choice a (position,
  key/value group); the query attends the rows ``s <= t`` of the chosen
  blocks, DENSE scores under that mask. ``y = W_o (o * sigmoid(W_g u))``.

Departures, each noted where it is made: the selection rule is BY
POSITION (the published kernels decide dense-or-sparse once for a whole
prefill by its length), so that the state after feeding ``[0, d)`` is a
function of those ``d`` tokens alone; the decay slopes, the pooling sizes
and the per-head output norm follow the family's public convention
(Lightning Attention-2 / MiniMax-01; MiniCPM4's ``sparse_config``) and
are listed under ``assumed`` in the configuration's file.

``blocks``, per sparse layer an (n, G, topk) array, hands the reference
the blocks the SYSTEM chose where an entry is not negative: a rounding
of the query moves the last chosen and the first unchosen block past
each other, and neither choice is wrong. The reference then says how far
each handed block stood below its own last free choice (``slack``) and
how many forced blocks the system left out (``forced_missing``).

Weights are upcast a layer (and a slice of the vocabulary) at a time;
attention and the feed-forward run in blocks of ``rows`` queries, and
the head over the positions from ``logits_from`` on alone, so that a
10k-token forward at full width fits beside the served weights.
Everything runs under ``default_matmul_precision("highest")``.
``weight_bits`` ((exponent bits, mantissa bits): every matrix rounded to
that float format first) and ``state_dtype`` exist to show what a lower
precision would read.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


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
    """x (n, h, d): half-split rotation over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (1.0 * theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rows(fn, n_rows, rows, *xs):
    """``fn`` over blocks of ``rows`` rows of each of ``xs`` (padded),
    one block at a time."""
    n = xs[0].shape[0]
    pad = -n % rows
    xs = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) for x in xs]
    xs = [x.reshape(-1, rows, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: fn(*a), tuple(xs))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:n_rows], out)


def decay(c: dict, layer: int):
    """lam_(l,h) for the published layer index ``l``: Lightning
    Attention-2's slopes, scaled down the depth as MiniMax-01 does."""
    h, total = c["lightning_nh"], c["total_layers"]
    s = 2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1) / h)
    return jnp.exp(-s * (1 - layer / max(total - 1, 1) + 1e-5))


@functools.partial(jax.jit, static_argnames=(
    "cfg", "layer", "state_at", "state_dtype", "weight_bits"))
def _lightning(p, scale, x, cfg, layer, state_at, state_dtype, weight_bits):
    c = dict(cfg)
    p, scale = _up(p, weight_bits), scale.astype(jnp.float32)
    n = x.shape[0]
    h, d = c["lightning_nh"], c["lightning_head_dim"]
    eps = c["rms_norm_eps"]
    pos = jnp.arange(n)
    u = _norm(x, scale, eps)
    q = _norm((u @ p["w_q"]).reshape(n, h, d), p["q_norm"], eps)
    k = _norm((u @ p["w_k"]).reshape(n, h, d), p["k_norm"], eps)
    v = (u @ p["w_v"]).reshape(n, h, d)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    lam = decay(c, layer)[:, None, None]

    def step(s, qkv):
        q_t, k_t, v_t = qkv
        s = lam * s.astype(jnp.float32) + k_t[:, :, None] * v_t[:, None, :]
        o = jnp.einsum("hd,hde->he", q_t, s) / math.sqrt(d)
        return s.astype(state_dtype), o

    s0 = jnp.zeros((h, d, d), state_dtype)
    cut = n if state_at is None else state_at
    s_at, o1 = jax.lax.scan(step, s0, (q[:cut], k[:cut], v[:cut]))
    _, o2 = jax.lax.scan(step, s_at, (q[cut:], k[cut:], v[cut:]))
    o = _norm(jnp.concatenate([o1, o2]), p["o_norm"], eps)
    y = (o.reshape(n, h * d) * jax.nn.sigmoid(u @ p["w_g"])) @ p["w_o"]
    r = c["scale_depth"] / math.sqrt(c["total_layers"])
    return x + r * y, s_at.astype(jnp.float32)


def _forced_and_free(c: dict, t, n_blocks: int):
    """(forced, free) masks (..., n_blocks) for queries at ``t`` (...)."""
    bs = c["sparse_block_size"]
    b = jnp.arange(n_blocks)
    tb = (t // bs)[..., None]
    newest = c["sparse_window_size"] // bs
    first = b < c["sparse_init_blocks"]
    near = (b > tb - newest) & (b <= tb)
    return first | near, ~first & (b <= tb - newest)


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "weight_bits"))
def _sparse(p, scale, x, given, cfg, rows, weight_bits):
    c = dict(cfg)
    p, scale = _up(p, weight_bits), scale.astype(jnp.float32)
    n = x.shape[0]
    h, g, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    a = h // g
    eps = c["rms_norm_eps"]
    ks, st = c["sparse_kernel_size"], c["sparse_kernel_stride"]
    bs, topk = c["sparse_block_size"], c["sparse_topk"]
    n_blocks = -(-n // bs)
    n_free = topk - c["sparse_init_blocks"] - c["sparse_window_size"] // bs
    u = _norm(x, scale, eps)
    q = _norm((u @ p["w_q"]).reshape(n, g, a, d), p["q_norm"], eps)
    k = _norm((u @ p["w_k"]).reshape(n, g, d), p["k_norm"], eps)
    v = (u @ p["w_v"]).reshape(n, g, d)
    gate = jax.nn.sigmoid(u @ p["w_g"])

    # pooled keys: window j is rows stride j ... stride j + kernel - 1
    m = max((n - ks) // st + 1, 1)
    at = jnp.minimum(st * jnp.arange(m)[:, None] + jnp.arange(ks), n - 1)
    pooled = k[at].mean(axis=1)  # (m, g, d)
    last_row = st * jnp.arange(m) + ks - 1
    # which windows overlap which block
    lo_w, hi_w = st * jnp.arange(m), last_row
    lo_b = bs * jnp.arange(n_blocks)
    overlap = (hi_w[None] >= lo_b[:, None]) & (lo_w[None] <= lo_b[:, None] + bs - 1)

    def block(q_blk, t, given_blk):
        # the choice, a (position, key/value group)
        s = jnp.einsum("qgad,jgd->qgaj", q_blk, pooled) / math.sqrt(d)
        present = (last_row[None] <= t[:, None]) & (last_row[None] < n)
        s = jnp.where(present[:, None, None], s, -1e30)
        prob = jnp.where(present[:, None, None], jax.nn.softmax(s, -1), 0.0)
        mass = prob.sum(2)  # (q, g, m)
        score = jnp.max(
            jnp.where(overlap[None, None], mass[:, :, None, :], 0.0), -1
        )  # (q, g, n_blocks)
        forced, free = _forced_and_free(c, t, n_blocks)
        forced, free = forced[:, None], free[:, None]
        cand = jnp.where(free, score, -jnp.inf)
        best, own = jax.lax.top_k(cand, min(n_free, n_blocks))
        ids = jnp.arange(n_blocks)
        own_mask = forced | (
            (own[..., None] == ids) & (best[..., None] > -jnp.inf)
        ).any(-2)
        handed = given_blk[:, :, :1] >= 0
        given_mask = (given_blk[..., None] == ids).any(-2)
        chosen = jnp.where(handed, given_mask, own_mask)
        # how far a handed free block stands below this reference's own
        # last free choice; a forced block the system left out
        kth = jnp.where(best[..., -1:] > -jnp.inf, best[..., -1:], 0.0)
        picked = jnp.take_along_axis(
            score, jnp.maximum(given_blk, 0), axis=-1
        )
        is_free = jnp.take_along_axis(
            jnp.broadcast_to(free, score.shape), jnp.maximum(given_blk, 0), -1
        ) & (given_blk >= 0)
        slack = jnp.where(is_free & handed, jnp.maximum(kth - picked, 0), 0.0)
        missing = jnp.where(
            handed[..., 0], (forced & ~given_mask).sum(-1), 0
        )
        # dense scores under the mask
        rows_ok = jnp.arange(n)[None] <= t[:, None]  # (q, n)
        by_block = jnp.repeat(chosen, bs, axis=-1)[..., :n]  # (q, g, n)
        sparse_row = (t >= c["sparse_dense_len"])[:, None, None]
        ok = rows_ok[:, None] & (by_block | ~sparse_row)
        sc = jnp.einsum("qgad,sgd->qgas", q_blk, k) / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(ok[:, :, None], sc, -jnp.inf), -1)
        o = jnp.einsum("qgas,sgd->qgad", w, v)
        own_ids = jnp.sort(jnp.where(
            own_mask, ids, n_blocks), -1)[..., :topk]
        return o, slack, missing, own_ids

    o, slack, missing, own = _rows(
        block, n, rows, q, jnp.arange(n), given
    )
    y = (o.reshape(n, h * d) * gate) @ p["w_o"]
    r = c["scale_depth"] / math.sqrt(c["total_layers"])
    return x + r * y, slack, missing, own


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "weight_bits"))
def _mlp(p, scale, x, cfg, rows, weight_bits):
    c = dict(cfg)
    p, scale = _up(p, weight_bits), scale.astype(jnp.float32)
    f = p["w_down"].shape[0]

    def block(x_blk):
        hid = _norm(x_blk, scale, c["rms_norm_eps"]) @ p["w_gate_up"]
        return (jax.nn.silu(hid[:, :f]) * hid[:, f:]) @ p["w_down"]

    r = c["scale_depth"] / math.sqrt(c["total_layers"])
    return x + r * _rows(block, x.shape[0], rows, x)


@functools.partial(jax.jit, static_argnames=("weight_bits",))
def _head(w, x, weight_bits):
    return x @ _up(w, weight_bits)


def settings(config) -> tuple:
    """The configuration's scalars as a hashable tuple: the published
    keys, MiniCPM4's ``sparse_config`` flattened to ``sparse_<key>``, and
    the cut (``first_layer``, ``total_layers``)."""
    c = dict(config)
    sparse = {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
              "block_size": 64, "window_size": 2048, "topk": 64,
              "dense_len": 8192, **(c.pop("sparse_config", None) or {})}
    for k, v in sparse.items():  # a flat key wins: a parsed config's own
        c.setdefault(f"sparse_{k}", v)
    c.setdefault("first_layer", 0)
    if c.get("total_layers") is None:
        c["total_layers"] = c["num_hidden_layers"]
    return tuple(sorted(
        (k, v) for k, v in c.items()
        if isinstance(v, (int, float, bool, str)) or v is None
    ))


def forward(params, tokens, config, *, blocks=None, logits_from: int = 0,
            state_at=None, rows: int = 256, vocab_slices: int = 8,
            state_dtype=jnp.float32, weight_bits=None,
            return_aux: bool = False):
    """tokens (n,) -> float32 logits (n - logits_from, vocab); with
    ``return_aux`` also {"state": per lightning layer the float32 state
    (H, d, d) after ``state_at`` tokens (all, when None), "slack": per
    sparse layer (n, G, topk), "forced_missing": per sparse layer (n, G),
    "blocks": per sparse layer this reference's own choice (n, G, topk),
    ascending, padded with the block count}."""
    cfg = settings(config)
    c = dict(cfg)
    mixers = list(config["mixer_types"])
    assert len(mixers) == c["num_hidden_layers"]
    n = tokens.shape[0]
    topk, g = c["sparse_topk"], c["num_key_value_heads"]
    aux = {"state": [], "slack": [], "forced_missing": [], "blocks": []}
    with jax.default_matmul_precision("highest"):
        x = c["scale_emb"] * params["embed"]["embedding"][tokens].astype(
            jnp.float32
        )
        for i, kind in enumerate(mixers):
            p, scale = params[f"mix{i}"], params[f"norm_mix{i}"]
            if kind == LIGHTNING:
                x, state = _lightning(p, scale, x, cfg, c["first_layer"] + i,
                                      state_at, state_dtype, weight_bits)
                aux["state"].append(state)
            elif kind == SPARSE:
                layer = len(aux["slack"])
                given = (jnp.asarray(blocks[layer], jnp.int32) if blocks
                         else jnp.full((n, g, topk), -1, jnp.int32))
                x, slack, missing, own = _sparse(p, scale, x, given, cfg,
                                                 rows, weight_bits)
                aux["slack"].append(slack)
                aux["forced_missing"].append(missing)
                aux["blocks"].append(own)
            else:
                raise ValueError(f"unknown mixer type {kind!r}")
            x = _mlp(params[f"mlp{i}"], params[f"norm_mlp{i}"], x, cfg,
                     rows, weight_bits)
        x = _norm(x[logits_from:], params["final_norm"].astype(jnp.float32),
                  c["rms_norm_eps"]) / (c["hidden_size"] / c["dim_model_base"])
        v = c["vocab_size"]
        step = -(-v // vocab_slices)
        logits = jnp.concatenate(
            [_head(params["w_head"][:, lo:lo + step], x, weight_bits)
             for lo in range(0, v, step)], axis=-1,
        )
    return (logits, aux) if return_aux else logits

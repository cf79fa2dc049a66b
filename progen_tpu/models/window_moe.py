"""A fourth model family: sliding-window and full attention side by side,
routed experts of which a layer holds a share.

The decoder of the ``afmoe`` model type as its public config describes it
(the key names of ``WindowMoEConfig`` are the published ones): sandwich
RMSNorm residual blocks, embeddings scaled by ``sqrt(hidden_size)`` where
``mup_enabled``, no biases, an untied head. ``layer_types`` names each
layer's attention:

* Grouped-query attention with gated outputs. ``q = W_q u``, ``k = W_k u``,
  ``v = W_v u``, ``g = W_g u``; ``q``, ``k`` RMS-normed per head; RoPE
  (half-split) on ``sliding_attention`` layers only, none on
  ``full_attention`` layers (the published modeling code's rule, which
  no config key states); softmax of ``q . k / sqrt(head_dim)``; a query
  at ``p`` sees the keys ``s <= p`` of a full layer and those with
  ``p - sliding_window < s <= p`` of a sliding one;
  ``y = W_o (o * sigmoid(g))``.
* Block: ``h = x + N(Attn(N(x)))``, ``x' = h + N(FFN(N(h)))``, four norms
  a layer, each with its own scale.
* Feed-forward. The first ``num_dense_layers`` layers are one SwiGLU of
  ``intermediate_size``. The others route over ``router_experts`` (the
  published ``num_experts``): ``latent_moe.route``'s sigmoid scores in
  float32, the top ``num_experts_per_tok`` of ``s + expert_bias``,
  weights the chosen ``s`` normalised (``route_norm``) and scaled
  (``route_scale``); beside ``num_shared_experts`` shared expert widths.
  A layer HOLDS ``num_experts`` of them, ``[first_expert, first_expert +
  num_experts)`` — one chip's share where experts are spread over chips —
  and computes only the assignments that land there: the others are
  dropped, with no stand-in for the chips that would hold them and no
  exchange (``latent_moe.grouped_experts``' held-share mask). Where
  ``num_experts`` equals ``router_experts`` the layer holds them all.

The decode mode (``config.decode``) feeds ``T >= 1`` positions of each of
``B`` rows through a ``cache`` collection whose state is sized by layer
kind: a sliding layer holds a RING of ``sliding_window + feed_rows`` rows
(``ring_k``, ``ring_v``), a full layer ``cache_len`` rows that grow with
the sequence (``k``, ``v``), each leaf (B, kv heads, rows, head_dim). A
position ``p`` lands in row ``p mod rows``; an aligned block of
``feed_rows`` never straddles a ring's end and overwrites only rows that
no query of the block can see. A row's position follows from the query's
alone, with no position stored beside it: row ``r`` holds
``p - ((p - r) mod rows)`` where that is not negative (a full layer's
rows are the same rule with no wrap). Positions and ``live`` are
ARGUMENTS, one per row and column, as in the other slot-batched families.
A block of rows attends a chunk of rows at a time with a running softmax,
up to the rows its positions reach; one row (the serving pool's decode
step) reads its whole leaves in one pass.

Mechanism classes (``jax.named_scope``, ``telemetry/scopes.py``): the
embedding and the head are ``head``; a layer's attention with its norms
and residual add is ``project`` (``project/gqa``), its reading of the
rows ``attend/window`` or ``attend/full`` and its row writes
``cache_write``; a feed-forward with its norms and residual add is
``ffn`` (``ffn/route``, ``ffn/experts``, ``ffn/shared``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from progen_tpu.config import _DTYPES
from progen_tpu.models.latent_moe import (DenseFFN, _init, _rms_norm, _rope,
                                          feed_blocks, grouped_experts, route)
from progen_tpu.models.layers import _update_at

SLIDING, FULL = "sliding_attention", "full_attention"
# the layer kinds RoPE turns (the published modeling code, not the config)
ROPE_KINDS = (SLIDING,)
# rows of keys one pass of a block's attention scores at once, at most
_KEY_CHUNK = 1024

_PUBLISHED_LAYERS = ((SLIDING,) * 3 + (FULL,)) * 15


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    family: str = "window_moe"
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    layer_types: tuple = _PUBLISHED_LAYERS
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    num_dense_layers: int = 6
    # the experts a layer HOLDS, from ``first_expert``; the router scores
    # ``router_experts`` (None: as many as it holds)
    num_experts: int = 256
    router_experts: Optional[int] = None
    first_expert: int = 0
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    # positions of one prefill block; a ring is ``sliding_window`` of them
    # more, so that a block is written where it stands before it attends
    feed_rows: int = 512
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    decode: bool = False
    # rows of a full layer's cache in decode mode (``decode_model`` sets it)
    cache_len: int = 0

    # what a published config may say and this family cannot compute
    _REFUSED = {"rope_scaling": None, "tie_word_embeddings": False,
                "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
                "num_expert_groups": 1, "num_limited_groups": 1,
                "attention_bias": False, "hidden_act": "silu"}

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"window_moe: {len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers"
            )
        for kind in self.layer_types:
            if kind not in (SLIDING, FULL):
                raise ValueError(
                    f"window_moe: unknown layer type {kind!r} (known: "
                    f"{SLIDING}, {FULL})"
                )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide the heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if self.sliding_window % self.feed_rows:
            raise ValueError(
                "window_moe: feed_rows must divide sliding_window (a block "
                "must not straddle a ring's end)"
            )
        if not (0 <= self.first_expert
                and self.first_expert + self.num_experts <= self.n_router
                and self.num_experts_per_tok <= self.n_router):
            raise ValueError(
                f"window_moe: the share [{self.first_expert}, "
                f"{self.first_expert + self.num_experts}) and top-"
                f"{self.num_experts_per_tok} must lie among the router's "
                f"{self.n_router} experts"
            )

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "WindowMoEConfig":
        for key, only in cls._REFUSED.items():
            if key in d and d[key] != only:
                raise ValueError(
                    f"window_moe: {key}={d[key]!r} is not supported "
                    f"(only {only!r})"
                )
        d = dict(d)
        if "layer_types" in d:
            d["layer_types"] = tuple(d["layer_types"])
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["layer_types"] = list(self.layer_types)
        return d

    # ----- what the serving and sampling layers ask of any family -------

    @property
    def num_tokens(self) -> int:
        return self.vocab_size

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def compute_dtype(self):
        return _DTYPES[self.dtype]

    @property
    def params_dtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def byte_codec(self) -> bool:
        """No: cli.serve takes and answers token ids for this family."""
        return False

    @property
    def n_router(self) -> int:
        """Experts the router scores: all of them, held here or not."""
        return self.router_experts or self.num_experts

    @property
    def n_expert_layers(self) -> int:
        return max(self.num_hidden_layers - self.num_dense_layers, 0)

    @property
    def ring_rows(self) -> int:
        """Rows of a sliding layer's cache: the window and one block."""
        return self.sliding_window + self.feed_rows

    # the names ``latent_moe.route`` reads
    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale


def decode_model(model: "WindowMoE", max_len: Optional[int] = None):
    """The decode-mode twin: same weight tree, full layers' caches of
    ``max_len`` rows rounded up to whole prefill blocks (a block is written
    where it stands, never clamped against the cache's end)."""
    c = model.config
    rows = int(max_len or c.max_position_embeddings)
    rows = -(-rows // c.feed_rows) * c.feed_rows
    return WindowMoE(dataclasses.replace(c, decode=True, cache_len=rows))


def _write_rows(buf, new, start, live=None):
    """Write each row's T consecutive entries ``new`` (B, G, T, d) into its
    cache ``buf`` (B, G, rows, d) from ``start`` (B,), keeping what is
    there wherever ``live`` (B, T) is False (None: all are written): one
    in-place update of the batch a leaf (``layers._update_at``; on a TPU
    the decode step's one row a slot is the row-write kernel)."""
    with jax.named_scope("cache_write"):
        if live is None:
            return jax.vmap(_update_at(1))(buf, new, start)
        return jax.vmap(_update_at(1, masked=True))(buf, new, start, live)


def held_position(t, rows, size: int):
    """The position row ``rows`` of a cache of ``size`` rows holds as a
    query at ``t`` sees it: the latest position of that row up to ``t``
    (negative: none yet)."""
    return t - jnp.mod(t - rows, size)


def visible(row_pos, t, window: Optional[int]):
    """Which rows at ``row_pos`` a query at ``t`` attends."""
    ok = (row_pos >= 0) & (row_pos <= t)
    if window is not None:
        ok = ok & (t - row_pos < window)
    return ok


def _key_chunk(rows: int, block: int) -> int:
    """The largest run of whole blocks up to ``_KEY_CHUNK`` rows that
    divides the cache."""
    n = rows // block
    return block * max(i for i in range(1, n + 1)
                       if n % i == 0 and i * block <= max(_KEY_CHUNK, block))


def _attend_rows(q, k, v, t, window: Optional[int], width: int):
    """T queries a row over the cache rows that hold their visible keys, a
    chunk of ``width`` rows at a time with a running softmax, up to the
    rows the largest position reaches: q (B, T, G, A, d), k, v (B, G, R,
    d), t (B, T) -> (B, T, G, A, d)."""
    b, tq, g, a, d = q.shape
    size = k.shape[2]
    scale = d ** -0.5

    def one(i, carry):
        top, total, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * width, width, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, i * width, width, axis=2)
        scores = jnp.einsum("btgad,bgsd->bgats", q, kc,
                            preferred_element_type=jnp.float32) * scale
        rows = i * width + jnp.arange(width)
        at = t[..., None]
        ok = visible(held_position(at, rows, size), at, window)[:, None, None]
        new_top = jnp.maximum(top, jnp.where(ok, scores, -1e30).max(-1))
        p = jnp.where(ok, jnp.exp(scores - new_top[..., None]), 0.0)
        rescale = jnp.exp(top - new_top)
        acc = acc * rescale[..., None] + jnp.einsum(
            "bgats,bgsd->bgatd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32)
        return new_top, total * rescale + p.sum(-1), acc

    shape = (b, g, a, tq)
    carry = (jnp.full(shape, -1e30, jnp.float32), jnp.zeros(shape, jnp.float32),
             jnp.zeros(shape + (d,), jnp.float32))
    if width == size:
        carry = one(0, carry)
    else:
        n_chunks = jnp.minimum((jnp.max(t) + width) // width, size // width)
        carry = jax.lax.fori_loop(0, n_chunks, one, carry)
    _, total, acc = carry
    o = acc / jnp.maximum(total, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).astype(q.dtype)


class WindowAttention(nn.Module):
    config: WindowMoEConfig
    kind: str

    @nn.compact
    def __call__(self, u, positions, live):
        c = self.config
        b, t, dm = u.shape
        h, g, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        pd = c.params_dtype
        w_q = self.param("w_q", _init(), (dm, h * d), pd)
        w_k = self.param("w_k", _init(), (dm, g * d), pd)
        w_v = self.param("w_v", _init(), (dm, g * d), pd)
        w_g = self.param("w_g", _init(), (dm, h * d), pd)
        w_o = self.param("w_o", _init(), (h * d, dm), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (d,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (d,), pd)
        sliding = self.kind == SLIDING
        with jax.named_scope("project/gqa"):
            q = _rms_norm((u @ w_q).reshape(b, t, h, d), q_norm, c.rms_norm_eps)
            k = _rms_norm((u @ w_k).reshape(b, t, g, d), k_norm, c.rms_norm_eps)
            v = (u @ w_v).reshape(b, t, g, d)
            if self.kind in ROPE_KINDS:
                q = _rope(q, positions, c.rope_theta, False)
                k = _rope(k, positions, c.rope_theta, False)
            q = q.reshape(b, t, g, h // g, d)
            k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # (B, G, T, d)
            gate = jax.nn.sigmoid(u @ w_g)
        window = c.sliding_window if sliding else None
        if not c.decode:
            # the sequence is its own cache, one row a position
            k_all, v_all, width = k, v, t
        else:
            rows = min(c.ring_rows, c.cache_len) if sliding else c.cache_len
            names = ("ring_k", "ring_v") if sliding else ("k", "v")
            cache_k, cache_v = (
                self.variable("cache", n, lambda: jnp.zeros((b, g, rows, d),
                                                            u.dtype))
                for n in names)
            k_all, v_all = cache_k.value, cache_v.value
            if not self.is_initializing():
                # rows are written before they are attended: a row sees the
                # rows of its own call that stand before it. One row writes
                # whether it is live or not: what a dead slot of the serving
                # pool holds means nothing until an admission rewrites its
                # whole tree
                start = positions[:, 0] % rows
                keep = None if t == 1 else live
                k_all = _write_rows(k_all, k, start, keep)
                v_all = _write_rows(v_all, v, start, keep)
                cache_k.value, cache_v.value = k_all, v_all
            width = rows if t == 1 else _key_chunk(rows, c.feed_rows)
        reading = (jax.named_scope("attend/window") if sliding
                   else jax.named_scope("attend/full"))
        with reading:
            o = _attend_rows(q, k_all, v_all, positions, window, width)
        with jax.named_scope("project/gqa"):
            y = (o.reshape(b, t, h * d) * gate) @ w_o
        self.sow("intermediates", "out", y)  # for the tests and the check
        return y


def held_experts(idx, config: WindowMoEConfig):
    """(experts counted from the first held, held (N, K) bool) of the
    router's choices ``idx`` (N, K)."""
    first = config.first_expert
    return idx - first, (idx >= first) & (idx < first + config.num_experts)


class HeldMoE(nn.Module):
    config: WindowMoEConfig

    @nn.compact
    def __call__(self, u, live):
        """u (B, T, D), live (B, T) -> (y, int32 (3,): held experts
        touched, the busiest one's rows, held assignments of live rows)."""
        c = self.config
        b, t, d = u.shape
        e, f = c.num_experts, c.moe_intermediate_size
        w_router = self.param("w_router", _init(), (d, c.n_router),
                              c.params_dtype)
        bias = self.param("expert_bias", nn.initializers.zeros, (c.n_router,),
                          jnp.float32)
        w_gate_up = self.param("w_gate_up", _init(), (e, d, 2 * f),
                               c.params_dtype)
        w_down = self.param("w_down", _init(), (e, f, d), c.params_dtype)
        x = u.reshape(b * t, d)
        alive = live.reshape(b * t)
        with jax.named_scope("ffn/route"):
            idx, w = route(x, w_router, bias, c)
            local, held = held_experts(idx, c)
        self.sow("intermediates", "experts", idx)  # for the tests
        with jax.named_scope("ffn/experts"):
            y, touched, load = grouped_experts(
                x, local, w, alive, w_gate_up, w_down, held=held
            )
            n_held = jnp.sum((alive[:, None] & held).astype(jnp.int32))
        with jax.named_scope("ffn/shared"):
            shared = DenseFFN(c, c.num_shared_experts * f, name="shared")(u)
        y = y.astype(u.dtype).reshape(b, t, d) + shared
        return y, jnp.stack([touched, load, n_held]).astype(jnp.int32)


class WindowMoE(nn.Module):
    config: WindowMoEConfig

    # the serving pool hands the decode step ALL slots as one batch with a
    # position per row (routing must see every live slot at once)
    slot_batched = True
    # the pool's state by kind, for ``ServeEngine.state_bytes``: a cache
    # leaf's name -> the gauge its bytes are counted under
    cache_kinds = {"ring_k": "window_cache_bytes",
                   "ring_v": "window_cache_bytes",
                   "k": "kv_cache_bytes", "v": "kv_cache_bytes"}

    @nn.compact
    def __call__(self, tokens, positions=None, live=None, head: bool = True):
        """tokens (B, T) int. Full-sequence mode: float32 logits
        (B, T, vocab). Decode mode: ``positions`` (B, T) int32, each row's
        T consecutive absolute positions (T > 1: from a multiple of
        ``feed_rows``), and ``live`` (B, T) bool or None (all) -> (logits
        or None where ``head`` is False, stats): stats is int32 (expert
        layers, 3), the held experts touched, the busiest one's rows and
        the held assignments of the live rows in each expert layer."""
        c = self.config
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        if live is None:
            live = jnp.ones((b, t), bool)
        eps, pd = c.rms_norm_eps, c.params_dtype

        def norm(name, x):
            scale = self.param(name, nn.initializers.ones, (c.hidden_size,), pd)
            return _rms_norm(x, scale, eps)

        with jax.named_scope("head"):
            x = nn.Embed(
                c.vocab_size, c.hidden_size, dtype=c.compute_dtype,
                param_dtype=pd, embedding_init=_init(), name="embed",
            )(tokens)
            if c.mup_enabled:
                x = x * math.sqrt(c.hidden_size)
        stats = []
        for i, kind in enumerate(c.layer_types):
            with jax.named_scope("project"):
                y = WindowAttention(c, kind, name=f"attn{i}")(
                    norm(f"input_norm{i}", x), positions, live)
                x = x + norm(f"post_attn_norm{i}", y)
            with jax.named_scope("ffn"):
                u = norm(f"pre_mlp_norm{i}", x)
                if i < c.num_dense_layers:
                    y = DenseFFN(c, c.intermediate_size, name=f"ffn{i}")(u)
                else:
                    y, st = HeldMoE(c, name=f"ffn{i}")(u, live)
                    stats.append(st)
                x = x + norm(f"post_mlp_norm{i}", y)
        with jax.named_scope("ffn"):
            stats = (jnp.stack(stats) if stats
                     else jnp.zeros((0, 3), jnp.int32))
        if c.decode:
            # what the blocks fed through this cache met, kept with it
            # until a decode step's read carries it to the host: per expert
            # layer the blocks, held experts touched, busiest rows, held
            # assignments and all assignments of their live rows
            fed = self.variable(
                "cache", "moe_feed",
                lambda: jnp.zeros((b, c.n_expert_layers, 5), jnp.int32),
            )
            if t > 1 and not self.is_initializing():
                with jax.named_scope("cache_write"):
                    assigned = jnp.sum(live.astype(jnp.int32)) * c.num_experts_per_tok
                    fed.value = fed.value + jnp.concatenate(
                        [jnp.ones_like(stats[:, :1]), stats,
                         jnp.broadcast_to(assigned, stats[:, :1].shape)],
                        axis=1)[None]
        logits = None
        if head or self.is_initializing():
            with jax.named_scope("head"):
                w_head = self.param("w_head", _init(),
                                    (c.hidden_size, c.vocab_size), pd)
                logits = jnp.dot(norm("final_norm", x), w_head,
                                 preferred_element_type=jnp.float32)
        return (logits, stats) if c.decode else logits

    # ----- what the cached decoders and the serving pool call ------------

    def feed_tokens(self, params, cache, tokens, lo, hi):
        """Feed positions ``[lo, hi)`` of ``tokens`` (B, L) through a
        decode cache in aligned blocks of ``feed_rows`` positions, one pass
        over the weights a block and no head (``sampling.feed_tokens`` is
        the contract: traced bounds, bit-equal under any split)."""
        return feed_blocks(self, params, cache, tokens, lo, hi)

    def decode_slots(self, params, cache, toks, pos, live):
        """One token for every slot of a pool whose cache leaves are
        stacked batch-1 trees (S, 1, ...): the slots are ONE batch with a
        position each. Returns (logits (S, vocab), the pool's new cache,
        int32 counts for the host: per expert layer the held experts
        touched, the busiest one's rows and the held assignments in this
        step, then the five counts of the prefill blocks whose caches
        entered the pool since the last step)."""
        (logits, stats), mut = self.apply(
            {"params": params,
             "cache": jax.tree.map(lambda c: c[:, 0], cache)},
            toks[:, None], pos[:, None], live[:, None], mutable=["cache"],
        )
        with jax.named_scope("sample"):  # the slots' bookkeeping
            new = dict(mut["cache"])
            fed = jnp.sum(new["moe_feed"], axis=0)
            new["moe_feed"] = jnp.zeros_like(new["moe_feed"])
            return (
                logits[:, 0],
                jax.tree.map(lambda c: c[:, None], new),
                jnp.concatenate([stats.reshape(-1), fed.reshape(-1)]),
            )

    def fold_counts(self, counts, n_live: int) -> dict:
        """What ``decode_slots`` reported for one step, as increments of
        the serving counters (host side, numpy)."""
        c = self.config
        n = c.n_expert_layers
        step = counts[: 3 * n].reshape(n, 3)
        fed = counts[3 * n:].reshape(n, 5)
        return {
            "moe_expert_layer_steps": n,
            "moe_assignments": n * n_live * c.num_experts_per_tok,
            "moe_held_assignments": int(step[:, 2].sum()),
            "moe_experts_touched": int(step[:, 0].sum()),
            "moe_max_load_rows": int(step[:, 1].sum()),
            "moe_feed_expert_layer_blocks": int(fed[:, 0].sum()),
            "moe_feed_experts_touched": int(fed[:, 1].sum()),
            "moe_feed_max_load_rows": int(fed[:, 2].sum()),
            "moe_feed_held_assignments": int(fed[:, 3].sum()),
            "moe_feed_assignments": int(fed[:, 4].sum()),
        }

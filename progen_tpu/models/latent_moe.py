"""A second model family: latent attention and routed experts.

The decoder of the ``deepseek_v3`` model type as public configs describe
it (the key names of ``LatentMoEConfig`` are the published ones): RMSNorm
pre-norm residual blocks, no biases, an untied head.

* Latent attention (MLA, ``q_lora_rank`` null). ``q = W_q u`` splits per
  head into ``[q_nope | q_rope]``; ``[c_kv | k_rope] = W_kva u``;
  ``c = RMSNorm(c_kv)``; per head ``[k_nope | v] = W_kvb c``. RoPE turns
  each head's ``q_rope`` and the ONE ``k_rope`` all heads share (with
  ``rope_interleave`` the pairs ``(2i, 2i+1)`` are first brought to the
  half-split layout, and stay there). What a position leaves behind is
  ``(c, RoPE(k_rope))``: ``kv_lora_rank + qk_rope_head_dim`` values a
  layer. The full-sequence forward expands keys and values; the decode
  mode absorbs ``W_kvb`` into the query and the output
  (``q' = q_nope W_kvb^K``, ``scores = q' . c + q_rope . k_rope``,
  ``o = (P c) W_kvb^V``) and attends over the cached latent rows.
* Feed-forward. The first ``first_k_dense_replace`` layers are one SwiGLU
  of ``intermediate_size``. The others route: ``s = sigmoid(W_r u)`` in
  float32, the experts are the top-k of ``s + e_score_correction_bias``,
  their weights the chosen ``s`` normalised to sum 1 and scaled by
  ``routed_scaling_factor``; ``FFN(u) = sum_i w_i E_i(u) + S(u)`` with
  ``S`` one SwiGLU of ``n_shared_experts`` expert widths.

Expert products are grouped by expert over ALL rows of a call (a prefill
block, or every slot of a decode step): assignments are sorted by expert
and one grouped product multiplies each expert's run of rows by its
weights, so an expert's weights are read once a layer a call and an
untouched expert's never. No assignment is dropped and there is no
capacity factor. A row's result does not depend on what else is in the
call (its own products, summed in its own top-k order), which is what
keeps a request's stream the same alone and in company, and a prefill
bit-equal under any split.

The decode mode (``config.decode``) feeds ``T >= 1`` positions of each of
``B`` rows through a ``cache`` collection: per layer ``c`` (B, cache_len,
kv_lora_rank) and ``k_rope`` (B, cache_len, qk_rope_head_dim). Positions
are an ARGUMENT, one per row and column, not a counter in the cache: the
serving pool's rows stand at different positions. ``live`` marks what is
fed; the rest is computed (shapes are static) but writes nothing and is
routed nowhere.

Mechanism classes (``jax.named_scope``, ``telemetry/scopes.py``): the
embedding and the head are ``head``; a layer's attention with its norm
and residual add is ``project`` (``project/mla``), its reading of the
latent rows ``attend/mla`` and its row writes ``cache_write``
(``_write_rows``); a layer's feed-forward with its norm and residual add
is ``ffn`` (``ffn/route``, ``ffn/experts``, ``ffn/shared``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from progen_tpu.config import _DTYPES
from progen_tpu.models.layers import _update_at

# Rows of one prefill block: on a v5e a pass over the weights is bound by
# reading them up to about 240 rows (sampling._FEED_ROWS has the readings)
FEED_ROWS = 128


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    family: str = "latent_moe"
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_interleave: bool = True
    max_position_embeddings: int = 32768
    # published and served in bfloat16: no float32 copy anywhere
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    decode: bool = False
    # rows of the latent cache in decode mode (``decode_model`` sets it)
    cache_len: int = 0

    # what a published config may say and this family cannot compute
    _REFUSED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
                "scoring_func": "sigmoid", "attention_bias": False,
                "rope_scaling": None, "tie_word_embeddings": False,
                "moe_layer_freq": 1, "hidden_act": "silu",
                "topk_method": "noaux_tc"}

    def __post_init__(self):
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "LatentMoEConfig":
        for key, only in cls._REFUSED.items():
            if key in d and d[key] != only:
                raise ValueError(
                    f"latent_moe: {key}={d[key]!r} is not supported "
                    f"(only {only!r})"
                )
        heads = d.get("num_attention_heads")
        if d.get("num_key_value_heads", heads) != heads:
            raise ValueError("latent_moe: every head has its own keys")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

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
    def n_expert_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)

    @property
    def byte_codec(self) -> bool:
        """No: ids of a 128k vocabulary are not bytes; cli.serve takes
        and answers token ids for this family."""
        return False

    @property
    def feed_rows(self) -> int:
        """Positions of one prefill block (``sampling.feed_width``)."""
        return FEED_ROWS

    @property
    def latent_row_values(self) -> int:
        """Values one position leaves in one layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def decode_model(model: "LatentMoE", max_len: Optional[int] = None):
    """The decode-mode twin: same weight tree, a latent cache of
    ``max_len`` rows rounded up to whole prefill blocks (a block is
    written where it stands, never clamped against the cache's end)."""
    c = model.config
    rows = int(max_len or c.max_position_embeddings)
    rows = -(-rows // FEED_ROWS) * FEED_ROWS
    return LatentMoE(dataclasses.replace(c, decode=True, cache_len=rows))


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, positions, theta, interleave):
    """x (B, T, H, R) at ``positions`` (B, T). Half-split rotation; an
    interleaved layout is first permuted to it, as the published code
    does (queries and keys alike, so their products agree)."""
    r = x.shape[-1]
    if interleave:
        x = x.reshape(*x.shape[:-1], r // 2, 2)
        x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], r)
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., r // 2:], xf[..., : r // 2]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _swiglu(x, w_gate_up, w_down):
    h = x @ w_gate_up
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _write_rows(buf, new, positions, live):
    """Write each row's T consecutive positions into its cache rows,
    keeping what is there wherever ``live`` is False: one in-place update
    of the batch a leaf (``layers._update_at``, the mask its own operand),
    not the gather and scatter of a vmapped slice and update."""
    with jax.named_scope("cache_write"):
        if live is None:
            return jax.vmap(_update_at(0))(buf, new, positions[:, 0])
        return jax.vmap(_update_at(0, masked=True))(
            buf, new, positions[:, 0], live
        )


def grouped_experts(x, idx, weights, live, w_gate_up, w_down, held=None):
    """``sum_k weights[n, k] * E_idx[n, k](x[n])`` for the rows of ``x``
    (N, D) where ``live`` (N,), zero elsewhere. The N*K assignments are
    sorted by expert and each expert's run of rows is multiplied by its
    weights in one grouped product (``lax.ragged_dot``: on a TPU a
    grouped-matmul kernel that walks the groups that have rows, on a CPU
    a masked dense product). ``held`` (N, K) bool, where given, marks the
    assignments whose expert the weights hold (a layer holding a share of
    the experts the router chooses among); the others go to no expert, as
    a dead row's do. Returns (y (N, D) float32, experts touched, rows of
    the busiest expert)."""
    n, k = idx.shape
    e = w_gate_up.shape[0]
    keep = live[:, None] if held is None else live[:, None] & held
    flat = jnp.where(keep, idx, e).reshape(-1)  # dead: no expert
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    h = jax.lax.ragged_dot(x[order // k], w_gate_up, sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, sizes)
    # rows past the last group belong to no expert: whatever is there
    ys = jnp.where((jnp.arange(n * k) < jnp.sum(sizes))[:, None], ys, 0)
    # back to (row, its k-th choice): each row sums its own products in
    # its own top-k order, whatever else the call held
    y = ys[jnp.argsort(order)].reshape(n, k, -1).astype(jnp.float32)
    y = jnp.einsum("nk,nkd->nd", weights, y,
                   precision=jax.lax.Precision.HIGHEST)
    return y, jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)


def route(u, w_router, bias, config):
    """(experts (N, K) int32, weights (N, K) float32), all in float32."""
    c = config
    s = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * c.routed_scaling_factor


def _init():
    return nn.initializers.normal(stddev=0.02)  # the published range


def feed_blocks(model, params, cache, tokens, lo, hi):
    """``feed_tokens`` of a family whose decode mode takes positions and
    ``live`` as arguments: the aligned blocks of ``config.feed_rows``
    positions that ``[lo, hi)`` touches, each one ``model.apply`` without
    the head; rows of a block outside the range are not live, and rows
    past the buffer's end feed any token."""
    t = model.config.feed_rows
    b, last = tokens.shape[0], tokens.shape[-1] - 1

    def feed(blk, cache):
        with jax.named_scope("head"):  # the block's tokens and positions
            at = blk * t + jnp.arange(t)
            live = (at >= lo) & (at < hi)
            fed = tokens[:, jnp.minimum(at, last)]
        _, mut = model.apply(
            {"params": params, "cache": cache}, fed,
            jnp.broadcast_to(at, (b, t)), jnp.broadcast_to(live, (b, t)),
            head=False, mutable=["cache"],
        )
        return mut["cache"]

    return jax.lax.fori_loop(
        lo // t, jnp.where(hi > lo, -(-hi // t), lo // t), feed, cache
    )


class LatentAttention(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, u, positions, live):
        c = self.config
        b, t, d = u.shape
        h, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        r = c.kv_lora_rank
        pd = c.params_dtype
        w_q = self.param("w_q", _init(), (d, h * (dn + dr)), pd)
        w_kva = self.param("w_kva", _init(), (d, r + dr), pd)
        kv_scale = self.param("kv_norm", nn.initializers.ones, (r,), pd)
        w_kvb = self.param("w_kvb", _init(), (r, h * (dn + dv)), pd)
        w_o = self.param("w_o", _init(), (h * dv, d), pd)
        scale = (dn + dr) ** -0.5

        with jax.named_scope("project/mla"):
            q = (u @ w_q).reshape(b, t, h, dn + dr)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
            kva = u @ w_kva
            lat = _rms_norm(kva[..., :r], kv_scale, c.rms_norm_eps)
            q_rope = _rope(q_rope, positions, c.rope_theta, c.rope_interleave)
            k_rope = _rope(kva[..., None, r:], positions, c.rope_theta,
                           c.rope_interleave)[:, :, 0]
            w_kvb = w_kvb.reshape(r, h, dn + dv)

        if not c.decode:
            with jax.named_scope("project/mla"):
                kv = jnp.einsum("bsc,chn->bshn", lat, w_kvb)
                k_nope, v = kv[..., :dn], kv[..., dn:]
            with jax.named_scope("attend/mla"):
                scores = (
                    jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope,
                                 preferred_element_type=jnp.float32)
                ) * scale
                causal = positions[:, None, :, None] >= positions[:, None, None, :]
                p = jax.nn.softmax(
                    jnp.where(causal, scores, -jnp.inf), axis=-1
                ).astype(u.dtype)
                o = jnp.einsum("bhts,bshn->bthn", p, v)
        else:
            cache_c = self.variable(
                "cache", "c", lambda: jnp.zeros((b, c.cache_len, r), u.dtype)
            )
            cache_r = self.variable(
                "cache", "k_rope",
                lambda: jnp.zeros((b, c.cache_len, dr), u.dtype),
            )
            lat_all, rope_all = cache_c.value, cache_r.value
            if not self.is_initializing():
                # a block writes its rows before it attends: a row sees
                # the rows of its own call that stand before it
                lat_all = _write_rows(lat_all, lat, positions, live)
                rope_all = _write_rows(rope_all, k_rope, positions, live)
                cache_c.value, cache_r.value = lat_all, rope_all
            with jax.named_scope("project/mla"):
                q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_kvb[..., :dn])
            with jax.named_scope("attend/mla"):
                scores = (
                    jnp.einsum("bthc,bsc->bhts", q_lat, lat_all,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bthr,bsr->bhts", q_rope, rope_all,
                                 preferred_element_type=jnp.float32)
                ) * scale
                seen = (jnp.arange(c.cache_len)[None, None, None, :]
                        <= positions[:, None, :, None])
                p = jax.nn.softmax(
                    jnp.where(seen, scores, -jnp.inf), axis=-1
                ).astype(u.dtype)
                o_lat = jnp.einsum("bhts,bsc->bthc", p, lat_all)
            with jax.named_scope("project/mla"):
                o = jnp.einsum("bthc,chn->bthn", o_lat, w_kvb[..., dn:])
        with jax.named_scope("project/mla"):
            return o.reshape(b, t, h * dv) @ w_o


class DenseFFN(nn.Module):
    config: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, u):
        c = self.config
        d = u.shape[-1]
        w_gate_up = self.param("w_gate_up", _init(), (d, 2 * self.width),
                               c.params_dtype)
        w_down = self.param("w_down", _init(), (self.width, d),
                            c.params_dtype)
        return _swiglu(u, w_gate_up, w_down)


class MoEFFN(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, u, live):
        """u (B, T, D), live (B, T) or None -> (y, touched, max_load)."""
        c = self.config
        b, t, d = u.shape
        e, f = c.n_routed_experts, c.moe_intermediate_size
        w_router = self.param("w_router", _init(), (d, e), c.params_dtype)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (e,), jnp.float32)
        w_gate_up = self.param("w_gate_up", _init(), (e, d, 2 * f),
                               c.params_dtype)
        w_down = self.param("w_down", _init(), (e, f, d), c.params_dtype)
        x = u.reshape(b * t, d)
        alive = (jnp.ones((b * t,), bool) if live is None
                 else live.reshape(b * t))
        with jax.named_scope("ffn/route"):
            idx, w = route(x, w_router, bias, c)
        self.sow("intermediates", "experts", idx)  # for the tests
        with jax.named_scope("ffn/experts"):
            y, touched, load = grouped_experts(
                x, idx, w, alive, w_gate_up, w_down
            )
        with jax.named_scope("ffn/shared"):
            shared = DenseFFN(c, c.n_shared_experts * f, name="shared")(u)
        y = y.astype(u.dtype).reshape(b, t, d) + shared
        return y, touched, load


class LatentMoE(nn.Module):
    config: LatentMoEConfig

    # the serving pool hands the decode step ALL slots as one batch with a
    # position per row (routing must see every live slot at once)
    slot_batched = True
    # the pool's state by kind, for ``ServeEngine.state_bytes``: every
    # leaf of this family's cache counts as latent cache
    cache_kinds = {"*": "latent_cache_bytes"}

    @nn.compact
    def __call__(self, tokens, positions=None, live=None, head: bool = True):
        """tokens (B, T) int. Full-sequence mode: float32 logits
        (B, T, vocab). Decode mode: ``positions`` (B, T) int32, each row's
        T consecutive absolute positions, and ``live`` (B, T) bool or None
        (all) -> (logits or None where ``head`` is False, stats): stats is
        int32 (expert layers, 2), the experts touched and the busiest
        expert's rows in each expert layer of this call."""
        c = self.config
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        with jax.named_scope("head"):
            x = nn.Embed(
                c.vocab_size, c.hidden_size, dtype=c.compute_dtype,
                param_dtype=c.params_dtype, embedding_init=_init(),
                name="embed",
            )(tokens)
        stats = []
        for i in range(c.num_hidden_layers):
            scale = self.param(f"attn_norm{i}", nn.initializers.ones,
                               (c.hidden_size,), c.params_dtype)
            with jax.named_scope("project"):
                x = x + LatentAttention(c, name=f"attn{i}")(
                    _rms_norm(x, scale, c.rms_norm_eps), positions, live
                )
            scale = self.param(f"ffn_norm{i}", nn.initializers.ones,
                               (c.hidden_size,), c.params_dtype)
            with jax.named_scope("ffn"):
                u = _rms_norm(x, scale, c.rms_norm_eps)
                if i < c.first_k_dense_replace:
                    x = x + DenseFFN(c, c.intermediate_size,
                                     name=f"ffn{i}")(u)
                else:
                    y, touched, load = MoEFFN(c, name=f"ffn{i}")(u, live)
                    x = x + y
                    stats.append(jnp.stack([touched, load]))
        with jax.named_scope("ffn"):
            stats = (jnp.stack(stats).astype(jnp.int32) if stats
                     else jnp.zeros((0, 2), jnp.int32))
        if c.decode:
            # what the blocks fed through this cache met, kept with it
            # until a decode step's read carries it to the host: per
            # expert layer the blocks, experts touched, busiest rows
            fed = self.variable(
                "cache", "moe_feed",
                lambda: jnp.zeros((b, c.n_expert_layers, 3), jnp.int32),
            )
            if t > 1 and not self.is_initializing():
                with jax.named_scope("cache_write"):
                    fed.value = fed.value + jnp.concatenate(
                        [jnp.ones_like(stats[:, :1]), stats], axis=1
                    )[None]
        logits = None
        if head or self.is_initializing():
            with jax.named_scope("head"):
                scale = self.param("final_norm", nn.initializers.ones,
                                   (c.hidden_size,), c.params_dtype)
                w_head = self.param("w_head", _init(),
                                    (c.hidden_size, c.vocab_size),
                                    c.params_dtype)
                logits = jnp.dot(
                    _rms_norm(x, scale, c.rms_norm_eps), w_head,
                    preferred_element_type=jnp.float32,
                )
        return (logits, stats) if c.decode else logits

    # ----- what the cached decoders and the serving pool call ------------

    def feed_tokens(self, params, cache, tokens, lo, hi):
        """Feed positions ``[lo, hi)`` of ``tokens`` (B, L) through a
        decode cache in aligned blocks of ``FEED_ROWS`` positions, one
        pass over the weights a block and no head (``sampling.feed_tokens``
        is the contract: traced bounds, bit-equal under any split)."""
        return feed_blocks(self, params, cache, tokens, lo, hi)

    def decode_slots(self, params, cache, toks, pos, live):
        """One token for every slot of a pool whose cache leaves are
        stacked batch-1 trees (S, 1, ...): the slots are ONE batch with a
        position each. Returns (logits (S, vocab), the pool's new cache,
        int32 counts for the host: per expert layer the experts touched
        and the busiest expert's rows in this step, then blocks, experts
        touched and busiest rows of the prefill blocks whose caches
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
        the serving counters (host side, numpy): the layout of ``counts``
        is this family's own, so the engine only adds what is named."""
        c = self.config
        n = c.n_expert_layers
        step = counts[: 2 * n].reshape(n, 2)
        fed = counts[2 * n:].reshape(n, 3)
        return {
            "moe_expert_layer_steps": n,
            "moe_assignments": n * n_live * c.num_experts_per_tok,
            "moe_experts_touched": int(step[:, 0].sum()),
            "moe_max_load_rows": int(step[:, 1].sum()),
            "moe_feed_expert_layer_blocks": int(fed[:, 0].sum()),
            "moe_feed_experts_touched": int(fed[:, 1].sum()),
            "moe_feed_max_load_rows": int(fed[:, 2].sum()),
        }

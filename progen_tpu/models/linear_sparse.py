"""A third model family: linear-attention layers with a recurrent state
beside block-sparse attention layers with an index cache.

The decoder of the ``minicpm_sala`` model type as its public config
describes it (the key names of ``LinearSparseConfig`` are the published
ones; the sparse selection's sizes, which that config lacks, carry the
names of MiniCPM4's ``sparse_config``): RMSNorm pre-norm residual blocks
scaled by ``scale_depth / sqrt(L)``, embeddings scaled by ``scale_emb``,
logits over ``RMSNorm(h) / (hidden_size / dim_model_base)``, SwiGLU
feed-forwards, no biases, an untied head. ``mixer_types`` names each
layer's mixer:

* ``lightning-attn``. ``q, k, v = W u``; ``q, k`` RMS-normed per head and
  rotated (RoPE over the whole head, half-split); per head a float32
  state ``S_t = lam S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``,
  RMS-normed per head; ``y = W_o (o * sigmoid(W_g u))``. The decay
  ``lam_(l,h) = exp(-s_h (1 - l/(L-1) + 1e-5))``, ``s_h = 2^(-8(h+1)/H)``
  takes the PUBLISHED layer index ``l = first_layer + i`` and the
  published depth ``L = total_layers``, whatever the cut. A block of T
  rows from state ``S`` is computed at once (``_lightning_block``): the
  decay-masked products inside the block plus the carried state, every
  power of ``lam`` as ``exp(-n s)`` (never a quotient of powers: the
  fast heads underflow); one row is the recurrence itself. The mixer's
  core runs in float32 whatever the model computes in: it is a few
  percent of a block's arithmetic and all of a state's precision.
* ``minicpm4``. Grouped-query attention without RoPE, ``q, k`` RMS-normed
  per head, an output gate. Every ``kernel_stride`` rows complete a
  pooled key: the mean of the last ``kernel_size`` keys of the group
  (the INDEX cache, ``ck``; entry ``m`` is the window that row
  ``stride m + stride - 1`` completes). A query at ``t < dense_len``
  attends every row ``s <= t``. From ``dense_len`` on it scores the
  present windows (softmax over them, summed over the heads of its
  key/value group), a block of ``block_size`` rows takes the largest
  score among the windows that overlap it, and the query attends the
  rows ``s <= t`` of ``topk`` blocks: the first ``init_blocks``, the
  ``window_size / block_size`` newest, and the best-scoring others
  (``_select_blocks``). The rule is by POSITION, so what feeding
  ``[0, d)`` leaves behind is a function of those d tokens alone.

The decode mode (``config.decode``) feeds ``T >= 1`` positions of each of
``B`` rows through a ``cache`` collection with two kinds of state: rows
that grow — per sparse layer ``k``, ``v`` (B, G x cache_len, d) and
``ck`` (B, G x cache_len / stride, d), a key/value group's rows after the
last group's so that a chosen block is one run of memory and the leaf
has no short axis for the compiler to move — and a recurrence of fixed
size, per lightning layer ``state`` (B, H, d, d) float32. Positions and
``live`` are ARGUMENTS, one per row and column (the serving pool's rows
stand at different positions); a block of T > 1 rows starts at a
multiple of T (``feed_tokens`` feeds aligned blocks). One row (the
serving pool's decode step) GATHERS its chosen blocks and never reads a
whole ``k`` / ``v`` leaf; a block of rows computes its scores over the
rows up to its own end, in chunks, under the selection's mask.

Mechanism classes (``jax.named_scope``, ``telemetry/scopes.py``): the
embedding and the head are ``head``; a layer's mixer with its norm and
residual add is ``project`` (``project/linear``, ``project/sparse``); the
recurrence (``attend/linear``), the index (``attend/index``: pool, score,
top-k, select), the attention (``attend/sparse``) and every read of
cached rows (``_slice_at``) are ``attend``; every row and pooled-key
write (``_write_rows``) is ``cache_write``; a SwiGLU with its norm and
residual add is ``ffn``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from progen_tpu.config import _DTYPES
from progen_tpu.models.latent_moe import (DenseFFN, _init, _rms_norm, _rope,
                                          feed_blocks)
from progen_tpu.models.layers import _update_at

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# the recurrence is kept in float32: rounded to the compute type at every
# token its error grows with the square root of a head's memory
STATE_DTYPE = jnp.float32
# rows of keys one pass of a block's attention scores at once
_KEY_CHUNK = 2048
_HIGHEST = jax.lax.Precision.HIGHEST

_PUBLISHED_MIXERS = (
    (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 3
)


@dataclasses.dataclass(frozen=True)
class LinearSparseConfig:
    family: str = "linear_sparse"
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple = _PUBLISHED_MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    # the cut: layer i here is published layer first_layer + i (its
    # decay), and total_layers (None: num_hidden_layers) is the L of the
    # residual scale and of the decay
    first_layer: int = 0
    total_layers: Optional[int] = None
    # MiniCPM4's ``sparse_config``, a field a key
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_init_blocks: int = 1
    sparse_block_size: int = 64
    sparse_window_size: int = 2048
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    # positions of one prefill block: 128 rows stay bound by reading the
    # weights (sampling._FEED_ROWS has the readings) and a 16k prompt
    # would make 128 passes over them
    feed_rows: int = 512
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    decode: bool = False
    # rows of the growing caches in decode mode (``decode_model`` sets it)
    cache_len: int = 0

    # what a published config may say and this family cannot compute
    _REFUSED = {"attention_bias": False, "attn_use_rope": False,
                "lightning_use_rope": True, "tie_word_embeddings": False,
                "hidden_act": "silu", "qk_norm": True,
                "use_output_gate": True, "use_output_norm": True,
                "attn_use_output_gate": True, "lightning_scale": "1/sqrt(d)",
                "rope_scaling": None}

    def __post_init__(self):
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(
                f"linear_sparse: {len(self.mixer_types)} mixer_types for "
                f"{self.num_hidden_layers} layers"
            )
        for kind in self.mixer_types:
            if kind not in (LIGHTNING, SPARSE):
                raise ValueError(
                    f"linear_sparse: unknown mixer type {kind!r} (known: "
                    f"{LIGHTNING}, {SPARSE})"
                )
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError(
                "linear_sparse: lightning_nkv != lightning_nh is not "
                "supported (every lightning head has its own keys)"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide the heads")
        st, bs = self.sparse_kernel_stride, self.sparse_block_size
        if (self.sparse_kernel_size != 2 * st or bs % st
                or self.sparse_window_size % bs or self.feed_rows % bs
                or self.sparse_topk
                <= self.sparse_init_blocks + self.sparse_window_size // bs):
            raise ValueError(
                "linear_sparse: sparse sizes must satisfy kernel_size = 2 x "
                "kernel_stride, stride | block_size | window_size, "
                "block_size | feed_rows, topk > init_blocks + window blocks"
            )

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "LinearSparseConfig":
        for key, only in cls._REFUSED.items():
            if key in d and d[key] != only:
                raise ValueError(
                    f"linear_sparse: {key}={d[key]!r} is not supported "
                    f"(only {only!r})"
                )
        d = dict(d)
        sparse = d.pop("sparse_config", None) or {}
        if sparse.get("use_nope", False):
            raise ValueError("linear_sparse: sparse_config.use_nope=True "
                             "is not supported (only False)")
        d.update({f"sparse_{k}": v for k, v in sparse.items()})
        if "mixer_types" in d:
            d["mixer_types"] = tuple(d["mixer_types"])
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mixer_types"] = list(self.mixer_types)
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
    def depth(self) -> int:
        """The L of the residual scale and of the decay."""
        return self.total_layers or self.num_hidden_layers

    @property
    def n_sparse_layers(self) -> int:
        return sum(kind == SPARSE for kind in self.mixer_types)


def decode_model(model: "LinearSparse", max_len: Optional[int] = None):
    """The decode-mode twin: same weight tree, growing caches of
    ``max_len`` rows rounded up to whole prefill blocks (a block is
    written where it stands, never clamped against the cache's end)."""
    c = model.config
    rows = int(max_len or c.max_position_embeddings)
    rows = -(-rows // c.feed_rows) * c.feed_rows
    return LinearSparse(dataclasses.replace(c, decode=True, cache_len=rows))


@functools.lru_cache(maxsize=None)
def _slice_at(size: int):
    """``dynamic_slice_in_dim`` of ``size`` rows along axis 0 with a
    batching rule of its own, the read beside ``layers._update_at``: one
    slice a row of the batch, written out. A vmapped slice with a start a
    row is a gather, and for its sake the TPU compiler re-lays the whole
    leaf out, the batch innermost, and back."""

    def plain(buf, start):
        return jax.lax.dynamic_slice_in_dim(buf, start, size, axis=0)

    sliced = jax.custom_batching.custom_vmap(plain)

    def read(buf, start):
        with jax.named_scope("attend"):
            return sliced(buf, start)

    @sliced.def_vmap
    def per_row(axis_size, in_batched, buf, start):
        buf, start = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((buf, start), in_batched)
        )
        # one slice of the whole batched leaf a row: a row picked out
        # first (``buf[i]``) is copied out whole before it is sliced
        zero = np.zeros((), start.dtype)
        return jnp.concatenate([
            jax.lax.dynamic_slice(
                buf, (np.asarray(i, start.dtype), start[i])
                + (zero,) * (buf.ndim - 2), (1, size) + buf.shape[2:])
            for i in range(axis_size)
        ]), True

    return read


def _grouped(rows, groups: int):
    """A cache leaf (B, G x S, d), a key/value group's rows after the
    last group's, as (B, G, S, d)."""
    b, gs, d = rows.shape
    return rows.reshape(b, groups, gs // groups, d)


def _write_rows(buf, new, start, live=None):
    """Write each row's T consecutive entries ``new`` (B, G, T, d) into
    its cache ``buf`` (B, G x S, d) from ``start`` (B,) of every group,
    keeping what is there wherever ``live`` (B, T) is False (None: all
    are written). One in-place update a row and group
    (``layers._update_at``), not a scatter. The leaf has no axis of G
    entries: with one the TPU compiler re-lays the whole leaf out, group
    innermost, for the sake of the one row written, and back, every
    step (3.2 GB each way at the benchmark's size)."""
    g, t = new.shape[1], new.shape[2]
    s = buf.shape[1] // g

    def one(b, n, p0, lv=None):
        for i in range(g):
            rows = n[i]
            if lv is not None:
                old = _slice_at(t)(b, i * s + p0)
                rows = jnp.where(lv[:, None], rows, old)
            b = _update_at(0)(b, rows, i * s + p0)
        return b

    with jax.named_scope("cache_write"):
        if live is None:
            return jax.vmap(one)(buf, new, start)
        return jax.vmap(one)(buf, new, start, live)


# ----- the lightning mixer's core, float32 throughout -----------------------


def decay_slopes(config: LinearSparseConfig, layer: int) -> np.ndarray:
    """``-log lam_(l,h)`` for the published layer index ``layer``, (H,)."""
    h = config.lightning_nh
    s = 2.0 ** (-8.0 * (np.arange(h) + 1) / h)
    return (s * (1 - layer / max(config.depth - 1, 1) + 1e-5)).astype(
        np.float32
    )


def _lightning_block(q, k, v, state, live, slopes):
    """T rows from ``state``. q, k, v (B, T, H, d) float32, state
    (B, H, d, d) float32, live (B, T), slopes (H,). Dead rows add nothing
    to the state and age nothing: row i stands ``c_i`` live rows after
    the state. Returns (o (B, T, H, d) unscaled, the state after the
    last live row)."""
    t = q.shape[1]
    c = jnp.cumsum(live.astype(jnp.float32), axis=1)  # (B, T)
    gap = c[:, :, None] - c[:, None, :]  # rows between j and i
    seen = (jnp.tril(jnp.ones((t, t), bool))[None] & live[:, None, :])
    s = slopes[None, :, None, None]
    weight = jnp.where(seen[:, None], jnp.exp(-s * gap[:, None]), 0.0)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k, precision=_HIGHEST)
    o = jnp.einsum("bhij,bjhd->bihd", scores * weight, v, precision=_HIGHEST)
    aged = jnp.exp(-slopes[None, None] * c[:, :, None])  # (B, T, H)
    o = o + jnp.einsum("bihd,bhde->bihe", q * aged[..., None], state,
                       precision=_HIGHEST)
    rest = c[:, -1:] - c  # live rows after row j
    w = jnp.where(live[..., None],
                  jnp.exp(-slopes[None, None] * rest[:, :, None]), 0.0)
    new = jnp.einsum("bjhd,bjhe->bhde", k * w[..., None], v,
                     precision=_HIGHEST)
    keep = jnp.exp(-slopes[None] * c[:, -1:])  # (B, H)
    return o, keep[..., None, None] * state + new


def _lightning_step(q, k, v, state, live, slopes):
    """One row: the recurrence itself. q, k, v (B, 1, H, d)."""
    lam = jnp.exp(-slopes)[None, :, None, None]
    new = lam * state + k[:, 0, :, :, None] * v[:, 0, :, None, :]
    state = jnp.where(live[:, :1, None, None], new, state)
    o = jnp.einsum("bhd,bhde->bhe", q[:, 0], state, precision=_HIGHEST)
    return o[:, None], state


def _lightning_sequence(q, k, v, slopes, block: int):
    """A whole sequence from a zero state, a block at a time."""
    b, t, h, d = q.shape
    pad = -t % block
    live = jnp.ones((b, t), bool)  # the padding is dead

    def blocks(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, -1, block, *x.shape[2:]), 1, 0)

    def one(state, xs):
        o, state = _lightning_block(*xs[:3], state, xs[3], slopes)
        return state, o

    _, o = jax.lax.scan(one, jnp.zeros((b, h, d, d), jnp.float32),
                        (blocks(q), blocks(k), blocks(v), blocks(live)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, d)[:, :t]


class LightningMixer(nn.Module):
    config: LinearSparseConfig
    layer: int  # the published index: it sets the decay

    @nn.compact
    def __call__(self, u, positions, live):
        c = self.config
        b, t, dm = u.shape
        h, d = c.lightning_nh, c.lightning_head_dim
        pd = c.params_dtype
        w_q, w_k, w_v, w_g = (
            self.param(n, _init(), (dm, h * d), pd)
            for n in ("w_q", "w_k", "w_v", "w_g")
        )
        w_o = self.param("w_o", _init(), (h * d, dm), pd)
        q_norm, k_norm, o_norm = (
            self.param(n, nn.initializers.ones, (d,), pd)
            for n in ("q_norm", "k_norm", "o_norm")
        )
        slopes = jnp.asarray(decay_slopes(c, self.layer))
        f32 = jnp.float32
        with jax.named_scope("project/linear"):
            q = _rms_norm((u @ w_q).reshape(b, t, h, d), q_norm, c.rms_norm_eps)
            k = _rms_norm((u @ w_k).reshape(b, t, h, d), k_norm, c.rms_norm_eps)
            v = (u @ w_v).reshape(b, t, h, d)
            q = _rope(q, positions, c.rope_theta, False).astype(f32)
            k = _rope(k, positions, c.rope_theta, False).astype(f32)
            v = v.astype(f32)
            gate = jax.nn.sigmoid(u @ w_g)
        with jax.named_scope("attend/linear"):
            if not c.decode:
                o = _lightning_sequence(q, k, v, slopes, c.feed_rows)
            else:
                kept = self.variable(
                    "cache", "state",
                    lambda: jnp.zeros((b, h, d, d), STATE_DTYPE),
                )
                step = _lightning_step if t == 1 else _lightning_block
                o, state = step(q, k, v, kept.value.astype(f32), live, slopes)
                if not self.is_initializing():
                    kept.value = state.astype(STATE_DTYPE)
        with jax.named_scope("project/linear"):
            o = _rms_norm(o / math.sqrt(d), o_norm.astype(f32), c.rms_norm_eps)
            return (o.reshape(b, t, h * d).astype(u.dtype) * gate) @ w_o


# ----- the sparse mixer's index, selection and attention --------------------


def _pool_windows(rows, stride: int):
    """rows (B, G, R, d), R a multiple of ``stride`` and the first
    ``stride`` rows the group BEFORE the first window's own: the R /
    stride - 1 pooled keys whose second halves the other groups are
    (each the mean of two consecutive groups), float32."""
    b, g, r, d = rows.shape
    halves = rows.astype(jnp.float32).reshape(b, g, r // stride, stride, d)
    halves = halves.sum(axis=3)
    return (halves[:, :, :-1] + halves[:, :, 1:]) / (2 * stride)


def _block_scores(q, ck, t, config: LinearSparseConfig):
    """q (B, T, G, A, d), the index ``ck`` (B, G, M, d), query positions
    ``t`` (B, T) -> (B, G, T, blocks) float32: per block the largest
    mass, summed over the group's A heads, of a present window that
    overlaps it. Window m is present once row stride m + stride - 1 is
    written and m >= 1 (entry 0 holds no window)."""
    c = config
    st, per = c.sparse_kernel_stride, c.sparse_block_size // c.sparse_kernel_stride
    b, g, m, d = ck.shape
    s = jnp.einsum("btgad,bgmd->bgtam", q, ck,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    at = jnp.arange(m)
    present = (at >= 1) & (st * at + st - 1 <= t[..., None])  # (B, T, M)
    present = present[:, None, :, None, :]
    mass = jnp.where(
        present, jax.nn.softmax(jnp.where(present, s, -1e30), axis=-1), 0.0
    ).sum(axis=3)  # (B, G, T, M)
    mass = mass.reshape(*mass.shape[:3], m // per, per)
    nxt = jnp.pad(mass[..., 1:, 0], [(0, 0)] * 3 + [(0, 1)])
    return jnp.maximum(mass.max(axis=-1), nxt)


def forced_blocks(t, n_blocks: int, config: LinearSparseConfig):
    """(forced, free) (..., n_blocks) for queries at ``t`` (...): the
    first blocks and the newest are always chosen; the others up to the
    newest compete."""
    c = config
    blk = jnp.arange(n_blocks)
    tb = (t // c.sparse_block_size)[..., None]
    newest = c.sparse_window_size // c.sparse_block_size
    first = blk < c.sparse_init_blocks
    near = (blk > tb - newest) & (blk <= tb)
    return first | near, ~first & (blk <= tb - newest)


def _select_blocks(score, t, config: LinearSparseConfig):
    """score (B, G, T, blocks), t (B, T) -> (ids (B, G, T, K) ascending,
    valid (B, G, T, K)): the forced blocks and the best-scoring free
    ones, K = min(topk, blocks) in all; where fewer exist the rest is
    not valid."""
    n_blocks = score.shape[-1]
    forced, free = forced_blocks(t, n_blocks, config)
    key = jnp.where(forced[:, None], jnp.inf,
                    jnp.where(free[:, None], score, -jnp.inf))
    best, ids = jax.lax.top_k(key, min(config.sparse_topk, n_blocks))
    ids = jnp.sort(jnp.where(best > -jnp.inf, ids, n_blocks), axis=-1)
    return jnp.minimum(ids, n_blocks - 1), ids < n_blocks


def _attend_chosen(q, k, v, ids, valid, t, config: LinearSparseConfig):
    """One query a row over its chosen blocks, GATHERED: q (B, G, A, d),
    cache leaves k, v (B, G x S, d), ids / valid (B, G, K), t (B,) ->
    (B, G, A, d)."""
    bs = config.sparse_block_size
    b, g, a, d = q.shape
    n_blocks = k.shape[1] // g // bs
    at = ids + n_blocks * jnp.arange(g)[None, :, None]  # a group's blocks

    def gather(rows):
        return jax.vmap(lambda x, i: x[i])(rows.reshape(b, -1, bs, d), at)

    kb, vb = gather(k), gather(v)  # (B, G, K, bs, d)
    scores = jnp.einsum("bgad,bgksd->bgaks", q, kb,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    rows = ids[..., None] * bs + jnp.arange(bs)
    ok = valid[..., None] & (rows <= t[:, None, None, None])  # (B, G, K, bs)
    scores = jnp.where(ok[:, :, None], scores, -jnp.inf)
    p = jax.nn.softmax(
        scores.reshape(*scores.shape[:3], -1), axis=-1
    ).reshape(scores.shape).astype(q.dtype)
    return jnp.einsum("bgaks,bgksd->bgad", p, vb,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attend_first(q, k, v, t):
    """One query a row over the first rows of its cache, all it sees
    while it stands below ``dense_len``: q (B, G, A, d), k, v (B, G, R,
    d), t (B,)."""
    d = q.shape[-1]
    scores = jnp.einsum("bgad,bgsd->bgas", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    seen = jnp.arange(k.shape[2]) <= t[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1).astype(q.dtype)
    return jnp.einsum("bgas,bgsd->bgad", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _key_chunk(rows: int, block: int) -> int:
    """The largest run of rows up to ``_KEY_CHUNK`` that is whole blocks
    and divides the cache."""
    n = rows // block
    return block * max(
        i for i in range(1, n + 1) if n % i == 0 and i * block <= max(
            _KEY_CHUNK, block)
    )


def _attend_rows(q, k, v, chosen, t, config: LinearSparseConfig):
    """T queries a row over the rows up to the block's end, scores under
    the mask a chunk of keys at a time with a running softmax (the cost
    follows the block's end, not the cache's length): q (B, T, G, A, d),
    k, v (B, G, S, d), ``chosen`` (B, G, T, blocks) bool, t (B, T) ->
    (B, T, G, A, d). A query below ``dense_len`` sees every row up to its
    own; from there on the rows of its chosen blocks."""
    c = config
    bs = c.sparse_block_size
    b, tq, g, a, d = q.shape
    width = _key_chunk(k.shape[2], bs)
    dense = (t < c.sparse_dense_len)[:, None, :, None]  # (B, 1, T, 1)

    def one(i, carry):
        top, total, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * width, width, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, i * width, width, axis=2)
        scores = jnp.einsum("btgad,bgsd->bgats", q, kc,
                            preferred_element_type=jnp.float32) / math.sqrt(d)
        rows = i * width + jnp.arange(width)
        picked = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            chosen, i * (width // bs), width // bs, axis=3), bs, axis=3)
        ok = (rows <= t[:, None, :, None]) & (dense | picked)  # (B, G, T, W)
        ok = ok[:, :, None]
        new_top = jnp.maximum(top, jnp.where(ok, scores, -1e30).max(-1))
        p = jnp.where(ok, jnp.exp(scores - new_top[..., None]), 0.0)
        scale = jnp.exp(top - new_top)
        acc = acc * scale[..., None] + jnp.einsum(
            "bgats,bgsd->bgatd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32)
        return new_top, total * scale + p.sum(-1), acc

    shape = (b, g, a, tq)
    n_chunks = (jnp.max(t) + width) // width
    _, total, acc = jax.lax.fori_loop(0, n_chunks, one, (
        jnp.full(shape, -1e30, jnp.float32), jnp.zeros(shape, jnp.float32),
        jnp.zeros(shape + (d,), jnp.float32),
    ))
    o = acc / jnp.maximum(total, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).astype(q.dtype)  # (B, T, G, A, d)


def _choose_for_rows(q, ck, t, config: LinearSparseConfig):
    """The selection of a block of rows as (ids, valid, chosen mask
    (B, G, T, blocks)); skipped (everything chosen) where no row of the
    call stands at ``dense_len`` or beyond."""
    c = config
    n_blocks = ck.shape[2] * c.sparse_kernel_stride // c.sparse_block_size
    k_sel = min(c.sparse_topk, n_blocks)
    b, tq, g = q.shape[:3]

    def select():
        ids, valid = _select_blocks(_block_scores(q, ck, t, c), t, c)
        hit = (ids[..., None] == jnp.arange(n_blocks)) & valid[..., None]
        return ids, valid, hit.any(axis=-2)

    def everything():
        return (jnp.zeros((b, g, tq, k_sel), jnp.int32),
                jnp.zeros((b, g, tq, k_sel), bool),
                jnp.ones((b, g, tq, n_blocks), bool))

    return jax.lax.cond(jnp.max(t) >= c.sparse_dense_len, select, everything)


def _row_counts(t, live, n_valid, config: LinearSparseConfig):
    """int32 (3,): over the live queries at ``t``, the rows they could
    see, the rows they attended and the blocks those lay in; ``n_valid``
    is a query's count of chosen blocks from ``dense_len`` on."""
    bs = config.sparse_block_size
    dense = t < config.sparse_dense_len
    blocks = jnp.where(dense, t // bs + 1, n_valid)
    rows = jnp.where(dense, t + 1, blocks * bs - (bs - 1 - t % bs))
    return jnp.stack([
        jnp.sum(jnp.where(live, x, 0)) for x in (t + 1, rows, blocks)
    ]).astype(jnp.int32)


class SparseMixer(nn.Module):
    config: LinearSparseConfig

    @nn.compact
    def __call__(self, u, positions, live):
        """-> (y, int32 (3,): rows visible, rows attended, blocks chosen,
        over the live queries of this call)."""
        c = self.config
        b, t, dm = u.shape
        h, g, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        a, st, bs = h // g, c.sparse_kernel_stride, c.sparse_block_size
        pd = c.params_dtype
        w_q = self.param("w_q", _init(), (dm, h * d), pd)
        w_k = self.param("w_k", _init(), (dm, g * d), pd)
        w_v = self.param("w_v", _init(), (dm, g * d), pd)
        w_g = self.param("w_g", _init(), (dm, h * d), pd)
        w_o = self.param("w_o", _init(), (h * d, dm), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (d,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (d,), pd)
        with jax.named_scope("project/sparse"):
            q = _rms_norm((u @ w_q).reshape(b, t, g, a, d), q_norm,
                          c.rms_norm_eps)
            k = _rms_norm((u @ w_k).reshape(b, t, g, d), k_norm, c.rms_norm_eps)
            k = jnp.swapaxes(k, 1, 2)  # (B, G, T, d)
            v = jnp.swapaxes((u @ w_v).reshape(b, t, g, d), 1, 2)
            gate = jax.nn.sigmoid(u @ w_g)
        if not c.decode:
            # the sequence is its own cache: padded to whole blocks, every
            # window pooled at once
            pad = -t % bs
            k_all, v_all = (jnp.pad(x, [(0, 0), (0, 0), (0, pad), (0, 0)])
                            for x in (k, v))
            with jax.named_scope("attend/index"):
                first = jnp.zeros_like(k_all[:, :, :1])  # entry 0: no window
                ck = jnp.concatenate(
                    [first, _pool_windows(k_all, st).astype(u.dtype)], axis=2)
                ids, valid, chosen = _choose_for_rows(q, ck, positions, c)
            with jax.named_scope("attend/sparse"):
                o = _attend_rows(q, k_all, v_all, chosen, positions, c)
        else:
            shape = (b, g * c.cache_len, d)
            cache_k = self.variable("cache", "k",
                                    lambda: jnp.zeros(shape, u.dtype))
            cache_v = self.variable("cache", "v",
                                    lambda: jnp.zeros(shape, u.dtype))
            cache_ck = self.variable(
                "cache", "ck",
                lambda: jnp.zeros((b, g * c.cache_len // st, d), u.dtype))
            k_all, v_all, ck = cache_k.value, cache_v.value, cache_ck.value
            start = positions[:, 0]
            if not self.is_initializing():
                # rows are written before they are attended: a row sees
                # the rows of its own call that stand before it. One row
                # writes whether it is live or not: what a dead row of the
                # serving pool holds means nothing until an admission
                # rewrites the slot's whole tree
                keep = None if t == 1 else live
                k_all = _write_rows(k_all, k, start, keep)
                v_all = _write_rows(v_all, v, start, keep)
                with jax.named_scope("attend/index"):
                    ck = self._pool(k_all, ck, start, live)
                cache_k.value, cache_v.value, cache_ck.value = k_all, v_all, ck
            if t == 1:
                o, ids, valid = self._one_row(q, k_all, v_all, ck, start, live)
            else:
                with jax.named_scope("attend/index"):
                    ids, valid, chosen = _choose_for_rows(
                        q, _grouped(ck, g), positions, c)
                with jax.named_scope("attend/sparse"):
                    o = _attend_rows(q, _grouped(k_all, g), _grouped(v_all, g),
                                     chosen, positions, c)
        # for the tests and the benchmark's check: the blocks chosen
        self.sow("intermediates", "blocks", jnp.where(valid, ids, -1))
        with jax.named_scope("attend/index"):
            counts = _row_counts(positions, live, valid[:, 0].sum(-1), c)
        with jax.named_scope("project/sparse"):
            return (o.reshape(b, t, h * d) * gate) @ w_o, counts

    def _pool(self, k_all, ck, start, live):
        """The pooled keys that this call's live rows complete, from the
        rows as the cache holds them (so that a window is the same
        function of its rows whichever call completes it)."""
        c = self.config
        st, g = c.sparse_kernel_stride, c.num_key_value_heads
        t = live.shape[1]

        def rows_from(x, at, n):  # n rows of every group from ``at``
            return jnp.stack([_slice_at(n)(x, i * c.cache_len + at)
                              for i in range(g)])

        if t == 1:
            # row p completes window p // stride when it is a group's
            # last; any other row writes entry 0, which holds no window
            m = start // st
            done = live[:, 0] & (start % st == st - 1) & (m >= 1)
            rows = jax.vmap(lambda x, at: rows_from(x, at, 2 * st))(
                k_all, jnp.maximum(st * (m - 1), 0))
            return _write_rows(ck, _pool_windows(rows, st).astype(ck.dtype),
                               jnp.where(done, m, 0))
        # an aligned block: the group before it and its own
        rows = jax.vmap(lambda x, at: jnp.concatenate(
            [rows_from(x, jnp.maximum(at - st, 0), st), rows_from(x, at, t)],
            axis=1))(k_all, start)
        return _write_rows(ck, _pool_windows(rows, st).astype(ck.dtype),
                           start // st, live[:, st - 1::st])

    def _one_row(self, q, k_all, v_all, ck, t, live):
        """The serving pool's decode step: each slot attends its chosen
        blocks, gathered; a slot still below ``dense_len`` the first
        rows of its cache, in a branch that runs only in a step that
        holds such a slot."""
        c = self.config
        q = q[:, 0]  # (B, G, A, d)
        first = min(c.sparse_dense_len, c.cache_len)
        b, g = q.shape[:2]

        def below():
            return _attend_first(q, _grouped(k_all, g)[:, :, :first],
                                 _grouped(v_all, g)[:, :, :first], t)

        if c.cache_len <= c.sparse_dense_len:  # no row ever selects
            with jax.named_scope("attend/sparse"):
                k_sel = min(c.sparse_topk, c.cache_len // c.sparse_block_size)
                return (below()[:, None], jnp.zeros((b, g, 1, k_sel), jnp.int32),
                        jnp.zeros((b, g, 1, k_sel), bool))
        with jax.named_scope("attend/index"):
            ids, valid = _select_blocks(
                _block_scores(q[:, None], _grouped(ck, g), t[:, None], c),
                t[:, None], c)
        with jax.named_scope("attend/sparse"):
            o = _attend_chosen(q, k_all, v_all, ids[:, :, 0], valid[:, :, 0],
                               t, c)
            dense = t < c.sparse_dense_len
            o_first = jax.lax.cond(
                jnp.any(dense & live[:, 0]), below, lambda: jnp.zeros_like(o))
            o = jnp.where(dense[:, None, None, None], o_first, o)
        return o[:, None], ids, valid


_WORD = 1 << 20  # the feed counters are carried as (high, low) words


class LinearSparse(nn.Module):
    config: LinearSparseConfig

    # the serving pool hands the decode step ALL slots as one batch with a
    # position per row
    slot_batched = True
    # the pool's state by kind, for ``ServeEngine.state_bytes``: a cache
    # leaf's name -> the gauge its bytes are counted under
    cache_kinds = {"k": "kv_cache_bytes", "v": "kv_cache_bytes",
                   "ck": "index_cache_bytes", "state": "linear_state_bytes"}

    @nn.compact
    def __call__(self, tokens, positions=None, live=None, head: bool = True):
        """tokens (B, T) int. Full-sequence mode: float32 logits
        (B, T, vocab). Decode mode: ``positions`` (B, T) int32, each row's
        T consecutive absolute positions (T > 1: from a multiple of T),
        and ``live`` (B, T) bool or None (all) -> (logits or None where
        ``head`` is False, counts): counts is int32 (sparse layers, 3),
        the rows the live queries of this call could see, the rows they
        attended and the blocks those lay in."""
        c = self.config
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        if live is None:
            live = jnp.ones((b, t), bool)
        with jax.named_scope("head"):
            x = c.scale_emb * nn.Embed(
                c.vocab_size, c.hidden_size, dtype=c.compute_dtype,
                param_dtype=c.params_dtype, embedding_init=_init(),
                name="embed",
            )(tokens)
        r = c.scale_depth / math.sqrt(c.depth)
        counts = []
        for i, kind in enumerate(c.mixer_types):
            scale = self.param(f"norm_mix{i}", nn.initializers.ones,
                               (c.hidden_size,), c.params_dtype)
            with jax.named_scope("project"):
                u = _rms_norm(x, scale, c.rms_norm_eps)
                if kind == LIGHTNING:
                    y = LightningMixer(c, c.first_layer + i, name=f"mix{i}")(
                        u, positions, live)
                else:
                    y, seen = SparseMixer(c, name=f"mix{i}")(
                        u, positions, live)
                    counts.append(seen)
                x = x + r * y
            scale = self.param(f"norm_mlp{i}", nn.initializers.ones,
                               (c.hidden_size,), c.params_dtype)
            with jax.named_scope("ffn"):
                x = x + r * DenseFFN(c, c.intermediate_size, name=f"mlp{i}")(
                    _rms_norm(x, scale, c.rms_norm_eps))
        with jax.named_scope("attend/index"):
            counts = (jnp.stack(counts) if counts
                      else jnp.zeros((0, 3), jnp.int32))
        if c.decode:
            # what the blocks fed through this cache met, kept with it
            # until a decode step's read carries it to the host: per
            # sparse layer the blocks, then rows visible, rows attended
            # and blocks chosen as (high, low) words of 2^20 — a long
            # prompt's row counts pass 32 bits
            fed = self.variable(
                "cache", "sparse_feed",
                lambda: jnp.zeros((b, c.n_sparse_layers, 7), jnp.int32),
            )
            if t > 1 and not self.is_initializing():
                with jax.named_scope("cache_write"):
                    words = jnp.stack(
                        [counts // _WORD, counts % _WORD], axis=-1
                    ).reshape(-1, 6)
                    total = fed.value + jnp.concatenate(
                        [jnp.ones_like(words[:, :1]), words], axis=1)[None]
                    carry = total[..., 2::2] // _WORD
                    total = total.at[..., 1::2].add(carry)
                    fed.value = total.at[..., 2::2].add(-carry * _WORD)
        logits = None
        if head or self.is_initializing():
            with jax.named_scope("head"):
                scale = self.param("final_norm", nn.initializers.ones,
                                   (c.hidden_size,), c.params_dtype)
                w_head = self.param("w_head", _init(),
                                    (c.hidden_size, c.vocab_size),
                                    c.params_dtype)
                out = _rms_norm(x, scale, c.rms_norm_eps) / (
                    c.hidden_size / c.dim_model_base)
                logits = jnp.dot(out, w_head,
                                 preferred_element_type=jnp.float32)
        return (logits, counts) if c.decode else logits

    # ----- what the cached decoders and the serving pool call ------------

    def feed_tokens(self, params, cache, tokens, lo, hi):
        """Feed positions ``[lo, hi)`` of ``tokens`` (B, L) through a
        decode cache in aligned blocks of ``feed_rows`` positions, one
        pass over the weights a block and no head; ``lo`` / ``hi`` are
        traced. Rows of a block outside ``[lo, hi)`` are dead: they add
        nothing to a recurrent state, write no row and complete no pooled
        window. What a split leaves behind (``sampling.feed_tokens``
        promises bit-equality under every split for the other families;
        a float32 recurrence cannot): split on block boundaries, the
        cache is bit-equal; split inside a block, the recurrent state is
        equal to float32 rounding (the sum over the block is cut in two
        and each half rounded), and so is what it feeds — the rows and
        pooled keys of a sparse layer that stands BEFORE the first
        lightning layer stay bit-equal, those behind one are equal to
        the rounding of the compute type. A stream is the same alone and
        in company either way."""
        return feed_blocks(self, params, cache, tokens, lo, hi)

    def decode_slots(self, params, cache, toks, pos, live):
        """One token for every slot of a pool whose cache leaves are
        stacked batch-1 trees (S, 1, ...): the slots are ONE batch with a
        position each. Returns (logits (S, vocab), the pool's new cache,
        int32 counts for the host: per sparse layer the rows visible,
        rows attended and blocks chosen in this step, then the seven
        words of the prefill blocks whose caches entered the pool since
        the last step)."""
        (logits, counts), mut = self.apply(
            {"params": params,
             "cache": jax.tree.map(lambda c: c[:, 0], cache)},
            toks[:, None], pos[:, None], live[:, None], mutable=["cache"],
        )
        with jax.named_scope("sample"):  # the slots' bookkeeping
            new = dict(mut["cache"])
            fed = jnp.sum(new["sparse_feed"], axis=0)
            new["sparse_feed"] = jnp.zeros_like(new["sparse_feed"])
            return (
                logits[:, 0],
                jax.tree.map(lambda c: c[:, None], new),
                jnp.concatenate([counts.reshape(-1), fed.reshape(-1)]),
            )

    def fold_counts(self, counts, n_live: int) -> dict:
        """What ``decode_slots`` reported for one step, as increments of
        the serving counters (host side, numpy)."""
        n = self.config.n_sparse_layers
        step = counts[: 3 * n].reshape(n, 3).sum(axis=0)
        fed = counts[3 * n:].reshape(n, 7).astype(np.int64).sum(axis=0)
        rows = [int(fed[i] * _WORD + fed[i + 1]) for i in (1, 3, 5)]
        return {
            "sparse_layer_steps": n,
            "sparse_rows_visible": int(step[0]),
            "sparse_rows_attended": int(step[1]),
            "sparse_blocks_selected": int(step[2]),
            "sparse_feed_layer_blocks": int(fed[0]),
            "sparse_feed_rows_visible": rows[0],
            "sparse_feed_rows_attended": rows[1],
            "sparse_feed_blocks_selected": rows[2],
        }

"""ProGen building blocks as flax.linen modules, batch-first, TPU-sharded.

Behavioral parity targets (cited into /root/reference/progen_transformer/):
  * LocalAttentionBlock  <- progen.py:50-103  (pre-LN, token-shift, bias-free
    fused QKV, RoPE on q/k/v, windowed attention, output projection)
  * FeedForwardBlock     <- progen.py:105-149 (pre-LN, token-shift, GLU or
    GELU, optional spatial gating, output projection)
  * SpatialGatingUnit    <- progen.py:151-185 (gate LayerNorm, learned causal
    (n, n) spatial mix with uniform ±eps/n init and ones bias)

Every weight carries flax logical-axis metadata so the whole model shards
through one rule table (progen_tpu/parallel/partition.py). LayerNorms are
scale-only (create_offset=False in the reference, progen.py:22).

Device work is filed under mechanism classes (``jax.named_scope``;
``telemetry/scopes.py``; the innermost class in an op's name stack wins):
``ProGen`` puts a whole attention block under ``project`` and a whole
feed-forward block under ``ffn``; here the attention proper (every
dispatch path) and the SGU's mix over its gate history are ``attend``,
and every cache write (``_write_rows``) is ``cache_write``.
"""

from __future__ import annotations

import collections
import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from progen_tpu.config import ProGenConfig
from progen_tpu.ops import pallas_decode_attention, pallas_row_write
from progen_tpu.ops.attention import local_attention
from progen_tpu.ops.pallas_decode_attention import (
    decode_attention,
    ring_attention,
)
from progen_tpu.ops.rotary import apply_rotary_pos_emb
from progen_tpu.ops.sgu import causal_sgu_mix
from progen_tpu.ops.shift import shift_tokens


def _dense_init():
    # Matches the scale of hk.Linear's default TruncatedNormal(1/sqrt(fan_in)).
    return nn.initializers.lecun_normal()


class DecodeRows(NamedTuple):
    """Where the T rows of one decode-mode call sit. A call feeds the
    block of T consecutive positions, aligned to a multiple of T, that
    holds the cache's position counter; T = 1 is the one-token decode
    step. ``live`` marks the rows that are fed — they alone write cache
    leaves, and no live row reads anything a dead row computed — or is
    None when every row is (which is how T = 1 stays free of masks)."""

    pos: jnp.ndarray  # (T,) int32 absolute position of each row
    live: Optional[jnp.ndarray]  # (T,) bool, a contiguous run; None = all


# A leaf the row-write kernel refuses is rewritten whole, in one select,
# where that moves at most this many bytes a slot: a pass over 256 KiB
# read and written costs about what one slot's update costs as an op of
# its own (about 1 us on a v5e, PERF.md §5), lanes padded to 128.
_SELECT_BYTES_PER_SLOT = 256 * 1024

# the counters ``row_write_paths`` hands out, innermost last
_PATH_COUNTERS: list = []


@contextlib.contextmanager
def row_write_paths():
    """Count, by path (``_row_write_path``), the batched row writes that
    ``_update_at``'s rule traces inside the block: a Counter, filled when
    the rule is traced, not when the program runs."""
    paths = collections.Counter()
    _PATH_COUNTERS.append(paths)
    try:
        yield paths
    finally:
        _PATH_COUNTERS.pop()


def _row_write_path(axis_size: int, buf, new, axis: int) -> str:
    """How the batched rule writes one row per slot at ``axis`` of the
    unbatched leaf, from what the operands show: on a TPU, with more than
    one slot and one row each, ``"kernel"`` where the row-write kernel
    fits the pooled leaf (``ops/pallas_row_write.py``: the row axis the
    second-minor, whole lanes and tiles) and ``"select"`` where the leaf
    is small enough to rewrite whole (ProGen's ``slot_pos``, written
    along its lanes); ``"loop"``, one update a slot, anywhere else."""
    if (
        axis_size == 1
        or not pallas_decode_attention.on_tpu()
        or new.shape[axis + 1] != 1
    ):
        return "loop"
    if pallas_row_write.fits(buf.shape, buf.dtype, axis + 1):
        return "kernel"
    if buf.size * buf.dtype.itemsize <= axis_size * _SELECT_BYTES_PER_SLOT:
        return "select"
    return "loop"


@functools.lru_cache(maxsize=None)
def _update_at(axis: int, masked: bool = False):
    """``dynamic_update_slice_in_dim`` along ``axis`` with a batching rule
    of its own. The serving pool vmaps the one-token apply over its slots,
    each with a start of its own; the plain update then becomes a scatter,
    which the TPU compiler runs as a serial loop over the slots — a bounds
    check, a row pick, a select and the update per slot, 74 such loops in
    a decode step of ProGen-large, a third of its device time (PERF.md
    §6). The rule writes every slot's row of a leaf in one call where it
    can — the row-write kernel, or one select over a small leaf
    (``_row_write_path``) —, else one update per slot, written out, which
    is the update alone. ``masked`` adds an operand ``live`` (the T rows,
    bool): a row that is not live keeps what ``buf`` holds."""

    def plain(buf, new, start, *live):
        if live:
            shape = [1] * new.ndim
            shape[axis] = new.shape[axis]
            old = jax.lax.dynamic_slice_in_dim(
                buf, start, new.shape[axis], axis=axis
            )
            new = jnp.where(live[0].reshape(shape), new, old)
        return jax.lax.dynamic_update_slice_in_dim(buf, new, start, axis=axis)

    update = jax.custom_batching.custom_vmap(plain)

    @update.def_vmap
    def per_slot(axis_size, in_batched, buf, new, start, *live):
        buf, new, start, *live = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((buf, new, start, *live), in_batched)
        )
        path = _row_write_path(axis_size, buf, new, axis)
        if _PATH_COUNTERS:
            _PATH_COUNTERS[-1][path] += 1
        if path == "kernel":
            return pallas_row_write.write_rows(
                buf, new, start, *(x[:, 0] for x in live),
                interpret=jax.default_backend() != "tpu",
            ), True
        size = buf.shape[axis + 1]
        if path == "select":
            at = jnp.where(start < 0, start + size, start).clip(0, size - 1)
            shape = (axis_size,) + (1,) * (buf.ndim - 1)
            hit = jax.lax.broadcasted_iota(
                at.dtype, buf.shape, axis + 1
            ) == at.reshape(shape)
            if live:
                hit = hit & live[0].reshape(shape)
            return jnp.where(hit, new, buf), True
        # lax primitives, bound directly: through ``jnp`` indexing and
        # ``lax.dynamic_update_slice`` each of the slots x leaves updates
        # (2,368 in a decode step of ProGen-large) pays their Python, which
        # more than doubles the step's trace time. What is traced is what
        # they would trace: a negative start wraps, per update — written
        # as one vector operation before the loop, the same updates take
        # 0.8 ms longer on a v5e (PERF.md, PR 29).
        zero = np.zeros((), start.dtype)
        size = np.asarray(size, start.dtype)
        at = [zero] * buf.ndim
        for s in range(axis_size):
            i = jax.lax.index_in_dim(start, s, keepdims=False)
            row = jax.lax.slice_in_dim(new, s, s + 1)
            at[0] = np.asarray(s, start.dtype)
            at[axis + 1] = jax.lax.select(
                jax.lax.lt(i, zero), jax.lax.add(i, size), i
            )
            if live:
                shape = [1] * row.ndim
                shape[axis + 1] = row.shape[axis + 1]
                old = jax.lax.dynamic_slice(buf, at, row.shape)
                keep = jax.lax.index_in_dim(live[0], s, keepdims=False)
                row = jnp.where(keep.reshape(shape), row, old)
            buf = jax.lax.dynamic_update_slice_p.bind(buf, row, *at)
        return buf, True

    return update


def _write_rows(buf, new, start, axis, rows: DecodeRows):
    """Write the block's T rows into ``buf`` at ``start`` along ``axis``,
    keeping what the buffer holds wherever a row is not live."""
    with jax.named_scope("cache_write"):
        t = rows.pos.shape[0]
        if rows.live is not None:
            shape = [1] * new.ndim
            shape[axis] = t
            old = jax.lax.dynamic_slice_in_dim(buf, start, t, axis=axis)
            new = jnp.where(rows.live.reshape(shape), new, old)
        return _update_at(axis % buf.ndim)(buf, new, start)


def _cached_shift(module: nn.Module, x: jnp.ndarray,
                  rows: DecodeRows) -> jnp.ndarray:
    """Token-shift in decode mode: row r takes row r - 1's shifted half,
    and the first row fed takes the cache variable that holds the
    post-LN features of the last position fed before this call (shared
    by the attention and feed-forward blocks), which the last row fed
    then replaces."""
    split = x.shape[-1] - x.shape[-1] // 2
    st = module.variable(
        "cache", "shift_state",
        lambda: jnp.zeros((x.shape[0], 1, split), x.dtype),
    )
    shifted = shift_tokens(x, shift_state=st.value)
    if rows.live is None:
        last = x[:, -1:, :split]
    else:
        # a resume mid-block reads the state too, not what a dead row
        # recomputed
        first = jnp.argmax(rows.live)
        n_live = jnp.sum(rows.live.astype(jnp.int32))
        at_first = (jnp.arange(x.shape[1]) == first)[None, :, None]
        shifted = jnp.concatenate(
            (jnp.where(at_first, st.value, shifted[..., :split]),
             shifted[..., split:]),
            axis=-1,
        )
        last = jax.lax.dynamic_slice_in_dim(
            x[..., :split], jnp.maximum(first + n_live - 1, 0), 1, axis=1
        )
        last = jnp.where(n_live > 0, last, st.value)
    if not module.is_initializing():
        st.value = last
    return shifted


class _NormScale(nn.Module):
    """Parameter-only twin of ScaleNorm's inner nn.LayerNorm: same module
    name ("norm"), same param ("scale": ones init, ("embed",) logical
    partitioning, param_dtype) but NO compute — the fused layer kernels
    (ops/pallas_layers.py) normalize in-register and only need the scale
    vector. Because the param path and metadata are identical, checkpoints
    interchange freely across config.use_fused_layer_kernels."""

    features: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
            (self.features,),
            self.param_dtype,
        )


class ScaleNorm(nn.Module):
    """Scale-only LayerNorm (hk.LayerNorm(create_scale=True, create_offset=False)).

    ``scale_only=True`` returns the scale PARAM instead of normalizing —
    the handle the fused Pallas paths use; only one of the two branches
    ever runs for a given (static) config, so the "norm" name is bound
    exactly once either way."""

    epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, scale_only: bool = False):
        if scale_only:
            return _NormScale(
                x.shape[-1], self.param_dtype, name="norm"
            )()
        return nn.LayerNorm(
            epsilon=self.epsilon,
            use_bias=False,
            use_scale=True,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones, ("embed",)
            ),
            name="norm",
        )(x)


def _fused_layer_ok(c: ProGenConfig) -> bool:
    """The fused layer kernels apply on the full-sequence path only: the
    decode cache keeps the unfused ops."""
    return c.use_fused_layer_kernels and not c.decode


def _norm_shift_head(module: nn.Module, x: jnp.ndarray,
                     rows: Optional[DecodeRows] = None) -> jnp.ndarray:
    """The pre-LN + token-shift head shared by the attention and FF
    blocks. With config.use_fused_layer_kernels the two ops run as ONE
    policy-dispatched Pallas pass (ops/pallas_layers.py); the norm's
    scale param is created through the same ScaleNorm module path either
    way, so the params tree is identical across the flag."""
    c = module.config
    norm = ScaleNorm(c.layer_norm_epsilon, c.compute_dtype, c.params_dtype)
    if c.shift_tokens and _fused_layer_ok(c):
        from progen_tpu.ops.pallas_layers import norm_shift

        return norm_shift(
            x, norm(x, scale_only=True),
            c.layer_norm_epsilon, c.compute_dtype,
            block_override=c.pallas_layer_block,
            interpret=jax.default_backend() != "tpu",
        )
    x = norm(x)
    if c.shift_tokens:
        x = _cached_shift(module, x, rows) if c.decode else shift_tokens(x)
    return x


class LocalAttentionBlock(nn.Module):
    """Windowed attention block. In config.decode mode the sequence axis
    holds the T positions of one block (``DecodeRows``; T = 1 for a
    decode step) and a rolling 2-window K/V cache (flax 'cache'
    collection) replaces the windowed reshape — O(2w·d) per position
    instead of a full forward (the reference samples with full-length
    forwards per token, utils.py:116-117)."""

    config: ProGenConfig
    # physical mesh, set by ProGen when built with one — enables the
    # explicit ring-collective attention path (config.use_ring_attn)
    mesh: object = None

    @nn.compact
    def __call__(self, x, sin, cos, rows: Optional[DecodeRows] = None):
        c = self.config
        b, n, _ = x.shape
        h, dh, w = c.heads, c.dim_head, c.window_size

        x = _norm_shift_head(self, x, rows)

        qkv = nn.Dense(
            3 * c.inner_dim,
            use_bias=False,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), ("embed", "qkv")
            ),
            name="to_qkv",
        )(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def split_heads(t):  # (b, n, h*dh) -> (b, h, n, dh); feature = (h, dh)
            return t.reshape(b, n, h, dh).transpose(0, 2, 1, 3)

        q, k, v = map(split_heads, (q, k, v))

        if c.decode:
            # slice the block's RoPE rows from the full tables
            sin = jax.lax.dynamic_slice_in_dim(sin, rows.pos[0], n, axis=0)
            cos = jax.lax.dynamic_slice_in_dim(cos, rows.pos[0], n, axis=0)

        q = apply_rotary_pos_emb(q, sin, cos)
        k = apply_rotary_pos_emb(k, sin, cos)
        if c.rotate_value:  # reference rotates v too (progen.py:87)
            v = apply_rotary_pos_emb(v, sin, cos)

        # one scope over every dispatch path: XLA, ring, or Pallas
        with jax.named_scope("attend/local"):
            if c.decode:
                out = self._decode_attend(q, k, v, rows)  # (b, h, T, dh)
            elif (
                c.use_ring_attn
                and self.mesh is not None
                and dict(getattr(self.mesh, "shape", {})).get("seq", 1) > 1
                and not self.is_initializing()
            ):
                # explicit one-hop halo exchange over the ``seq`` ring
                # instead of GSPMD-inferred collectives. Skipped during
                # init: the dummy init batch (1, L) doesn't divide over
                # the data axis, and the op is parameter-free so init
                # doesn't need it for shapes.
                from progen_tpu.parallel.ring_attention import (
                    ring_local_attention,
                )

                # use_pallas_attn composes: each ring shard runs the
                # measured kernel (halo-aware variant) instead of the XLA
                # dense path
                out = ring_local_attention(
                    q, k, v, window_size=w, mesh=self.mesh,
                    use_pallas=c.use_pallas_attn,
                )
            elif c.use_pallas_attn:
                from progen_tpu.ops.pallas_attention import (
                    measured_impls,
                    pallas_local_attention,
                )

                # positional args: custom_vjp nondiff_argnums are
                # positional. Mosaic-compiled on TPU; the interpreter is
                # the CPU test path and nothing else. use_pallas_attn means
                # "best measured kernel combo for this shape" —
                # per-direction winners from the policy table keyed on
                # (window, n, batch*heads); pallas_bh_block >= 1 (0 =
                # unset) overrides the policy's forward blocking, so an
                # explicit 1 can force one-window-per-program even where
                # the policy picked a batched forward.
                interpret = jax.default_backend() != "tpu"
                fwd_impl, bwd_impl, g = measured_impls(w, n=n, bh=b * h)
                if c.pallas_bh_block:
                    g = c.pallas_bh_block  # explicit config beats policy
                if fwd_impl == "xla" and bwd_impl == "xla":
                    # both directions lost on-chip at this shape: plain
                    # XLA autodiff (going through the custom VJP would
                    # recompute the forward inside the backward for
                    # nothing)
                    out = local_attention(q, k, v, window_size=w)
                else:
                    out = pallas_local_attention(
                        q, k, v, w, None, interpret, bwd_impl, g, fwd_impl
                    )
            else:
                out = local_attention(q, k, v, window_size=w)

        out = out.transpose(0, 2, 1, 3).reshape(b, n, c.inner_dim)
        out = nn.with_logical_constraint(out, ("batch", "seq_act", None))
        return nn.Dense(
            c.dim,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), ("qkv", "embed")
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)
            ),
            name="to_out",
        )(out)

    def _decode_attend(self, q, k, v, rows: DecodeRows):
        """Attention of the block's T queries against a rolling 2-window
        K/V ring buffer.

        Slot ``p % 2w`` holds position p; the block's keys are written
        first, then every query row is masked by the stored absolute
        positions against its own. Writing before attending is safe
        because a block never straddles a window boundary (T divides w
        and blocks are aligned): the slots it overwrites held window
        k - 2, which no query of window k sees, and the rows after a
        query in its own block are masked like any later position.
        Window-0 queries' softmax is diluted by exactly ``w`` phantom
        zero-score/zero-value keys via an analytic denominator
        correction — the reference's zero-padded previous window
        (progen.py:90-96) without materializing it.

        The arithmetic is ``ops/pallas_decode_attention.py``'s. A block
        of T > 1 rows (a prefill chunk, one slot's ring) and any
        unbatched one-token call (``sample_fast``) run its plain form,
        ``ring_attention``: scores over the whole ring, masked
        afterwards. The one-token call under the serving pool's ``vmap``
        over its slots — the engine's decode step — goes through
        ``decode_attention``, whose batching rule on a TPU is a kernel
        that reads, per slot, only the ring blocks in which that slot's
        query sees a row (the blocks listed from this cache's own
        ``slot_pos``), where the head size and window fit its tiling;
        off the TPU, and at the shapes of the tests' small models, the
        rule is the vmapped plain form.
        """
        c = self.config
        b, h, _, dh = q.shape
        w = c.window_size
        ring = 2 * w

        ck = self.variable(
            "cache", "k", lambda: jnp.zeros((b, h, ring, dh), q.dtype)
        )
        cv = self.variable(
            "cache", "v", lambda: jnp.zeros((b, h, ring, dh), q.dtype)
        )
        cpos = self.variable(
            "cache", "slot_pos", lambda: jnp.full((ring,), -1, jnp.int32)
        )

        pos = rows.pos
        slot = pos[0] % ring
        if not self.is_initializing():
            ck.value = _write_rows(ck.value, k, slot, 2, rows)
            cv.value = _write_rows(cv.value, v, slot, 2, rows)
            cpos.value = _write_rows(cpos.value, pos, slot, 0, rows)

        if q.shape[2] == 1:
            # the decode step: under the pool's ``vmap`` over its slots
            # this reads, per slot, the ring blocks its query sees
            return decode_attention(w)(
                q, ck.value, cv.value, cpos.value, pos
            )
        return ring_attention(q, ck.value, cv.value, cpos.value, pos, w)


class SpatialGatingUnit(nn.Module):
    config: ProGenConfig
    dim_out: int

    @nn.compact
    def __call__(self, x, rows: Optional[DecodeRows] = None):
        c = self.config
        n = c.seq_len
        assert c.decode or x.shape[-2] == n, (
            f"SGU is bound to seq_len={n} at init, got sequence {x.shape[-2]}"
        )
        x, gate = jnp.split(x, 2, axis=-1)

        norm = ScaleNorm(c.layer_norm_epsilon, c.compute_dtype, c.params_dtype)
        fused = _fused_layer_ok(c)
        # the fused tail normalizes the gate in-kernel; every other path
        # (incl. decode's gate_history, which stores NORMALIZED gates)
        # normalizes here
        gate_scale = norm(gate, scale_only=True) if fused else None
        if not fused:
            gate = norm(gate)

        init_scale = c.sgu_init_eps / n

        def symmetric_uniform(key, shape, dtype):
            return jax.random.uniform(
                key, shape, dtype, minval=-init_scale, maxval=init_scale
            )

        weights = self.param(
            "spatial_weights",
            nn.with_logical_partitioning(
                symmetric_uniform, ("sgu_seq_out", "sgu_seq_in")
            ),
            (n, n),
            c.params_dtype,
        )
        biases = self.param(
            "spatial_biases",
            nn.with_logical_partitioning(nn.initializers.ones, ("sgu_seq_out", None)),
            (n, 1),
            c.params_dtype,
        )

        with jax.named_scope("attend/sgu"):
            if c.decode:
                # incremental spatial mix: keep the LayerNormed gate
                # history and contract the block's causal rows of the
                # (n, n) matrix with it —
                # out[p] = sum_{j<=p} W[p, j] * gate[j] + b[p]
                b_sz, t, half = gate.shape
                hist = self.variable(
                    "cache", "gate_history",
                    lambda: jnp.zeros((b_sz, n, half), jnp.float32),
                )
                pos = rows.pos
                if not self.is_initializing():
                    hist.value = _write_rows(
                        hist.value, gate.astype(jnp.float32), pos[0], 1,
                        rows,
                    )
                w_rows = jax.lax.dynamic_slice_in_dim(
                    weights, pos[0], t, axis=0
                ).astype(jnp.float32)
                w_rows = jnp.where(
                    jnp.arange(n) <= pos[:, None], w_rows, 0.0
                )
                mixed = jnp.einsum("bnd,tn->btd", hist.value, w_rows)
                mixed = mixed + jax.lax.dynamic_slice_in_dim(
                    biases, pos[0], t, axis=0
                ).astype(jnp.float32)
                x = x * mixed.astype(x.dtype)
            elif fused:
                from progen_tpu.ops.pallas_layers import sgu_mix_gate

                x = sgu_mix_gate(
                    x, gate, weights, biases, gate_scale,
                    c.layer_norm_epsilon, c.compute_dtype,
                    block_override=c.pallas_layer_block,
                    interpret=jax.default_backend() != "tpu",
                )
            else:
                gate = causal_sgu_mix(
                    gate, weights, biases, c.sgu_block_size
                ).astype(x.dtype)
                x = x * gate
        return nn.Dense(
            self.dim_out,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(), ("sgu_hidden", "mlp")
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("mlp",)),
            name="proj_out",
        )(x)


class FeedForwardBlock(nn.Module):
    config: ProGenConfig
    glu: bool = False
    spatial_gate: bool = False

    @nn.compact
    def __call__(self, x, rows: Optional[DecodeRows] = None):
        c = self.config
        assert not (self.glu and self.spatial_gate), (
            "glu and sgu cannot be turned on at the same time"
        )
        hidden = c.dim * c.ff_mult * (2 if self.glu else 1)

        x = _norm_shift_head(self, x, rows)

        x = nn.Dense(
            hidden,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            kernel_init=nn.with_logical_partitioning(_dense_init(), ("embed", "mlp")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("mlp",)),
            name="proj_in",
        )(x)

        if self.glu:
            x, gate = jnp.split(x, 2, axis=-1)
            x = x * jax.nn.gelu(gate)
        else:
            x = jax.nn.gelu(x)

        if self.spatial_gate:
            x = SpatialGatingUnit(c, dim_out=hidden // 2, name="sgu")(x, rows)

        x = nn.with_logical_constraint(x, ("batch", "seq_act", "mlp_act"))
        return nn.Dense(
            c.dim,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            kernel_init=nn.with_logical_partitioning(_dense_init(), ("mlp", "embed")),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            name="proj_out",
        )(x)

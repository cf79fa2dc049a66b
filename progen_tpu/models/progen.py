"""The ProGen model: a decoder-only protein LM, batch-first, TPU-sharded.

Architecture parity with /root/reference/progen_transformer/progen.py:187-233:
token embed -> depth x (LocalAttention + FeedForward) with residual adds,
the last `global_mlp_depth` layers using gMLP (spatial-gate) feed-forwards
with GLU disabled (progen.py:211-212), then scale-only LayerNorm + linear
logits head (no weight tying).

TPU-first deltas:
  * real leading batch axis (the reference is single-sequence + external vmap,
    progen.py:224-227) so XLA sees one large MXU-friendly program;
  * mixed precision bf16 compute / f32 params / f32 logits (the jmp policy of
    progen.py:235 with bf16, which is native to the MXU);
  * flax logical-axis metadata on every weight, consumed by
    progen_tpu/parallel/partition.py to lay the model over a device mesh;
  * optional per-block rematerialization (config.remat) to trade FLOPs for
    HBM during backprop;
  * optional lax.scan over the uniform blocks (config.scan_layers) for
    O(1)-in-depth compile;
  * embedding init truncated_normal(stddev=0.02) — a deliberate delta from
    hk.Embed's TruncatedNormal(stddev=1.0) default (ref progen.py:207);
    the GPT-style small init trains more stably. Weight-transplant parity
    tests are init-independent (tests/test_reference_parity.py).

Mechanism classes (``jax.named_scope``, ``telemetry/scopes.py``): the
embedding, final norm and logits are ``head``; each attention block with
its residual add (and the decode positions and RoPE tables it reads) is
``project``, each feed-forward block with its residual add ``ffn``;
``layers.py`` files the attention proper and the SGU mix under
``attend`` and the cache writes under ``cache_write``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from progen_tpu.config import ProGenConfig
from progen_tpu.models.layers import (
    DecodeRows,
    FeedForwardBlock,
    LocalAttentionBlock,
    ScaleNorm,
)
from progen_tpu.ops.rotary import fixed_pos_embedding


class UniformBlock(nn.Module):
    """One attention+FF residual pair — the scan body for the uniform
    (non-gMLP) prefix of the stack when config.scan_layers is set."""

    config: ProGenConfig
    glu: bool
    mesh: object = None

    @nn.compact
    def __call__(self, x, sin, cos):
        c = self.config
        with jax.named_scope("project"):
            x = x + LocalAttentionBlock(c, mesh=self.mesh, name="attn")(
                x, sin, cos, None
            )
        with jax.named_scope("ffn"):
            x = x + FeedForwardBlock(c, glu=self.glu, name="ff")(x, None)
            x = nn.with_logical_constraint(
                x, ("batch", "seq_act", "embed_act")
            )
        return x, None


def decode_model(model: "ProGen") -> "ProGen":
    """The decode-mode twin of a full-forward model: same weight tree
    (scan-stacked layouts convert via ``unstack_params`` — decode is always
    unrolled because its per-layer caches are), one token or one aligned
    block of positions per call (``ProGen.__call__``), state in a flax
    'cache' collection (rolling 2-window K/V ring, token-shift states,
    SGU gate history, and a position counter — all allocated
    batch-shaped by ``init``, which is the cache-shape hook the sampling
    and serving layers build their buffers from)."""
    import dataclasses

    return ProGen(dataclasses.replace(model.config, decode=True),
                  mesh=model.mesh)


def unstack_params(params: dict, config: ProGenConfig) -> dict:
    """Convert a scan_layers param tree (stacked 'layers' subtree) to the
    unrolled attn{i}/ff{i} layout — needed by decode mode (per-layer caches
    are unrolled) and by checkpoint interchange with non-scan configs."""
    import jax

    if "layers" not in params:
        return params
    n_uniform = config.depth - config.global_mlp_depth
    out = {k: v for k, v in params.items() if k != "layers"}
    stacked = params["layers"]
    for i in range(n_uniform):
        out[f"attn{i}"] = jax.tree.map(lambda x: x[i], stacked["attn"])
        out[f"ff{i}"] = jax.tree.map(lambda x: x[i], stacked["ff"])
    return out


def stack_params(params: dict, config: ProGenConfig) -> dict:
    """Inverse of unstack_params: unrolled attn{i}/ff{i} -> stacked
    'layers' subtree for a scan_layers model."""
    import jax
    import jax.numpy as jnp

    n_uniform = config.depth - config.global_mlp_depth
    if n_uniform < 1 or "layers" in params:
        return params
    out = {
        k: v
        for k, v in params.items()
        if not any(
            k == f"{p}{i}" for p in ("attn", "ff") for i in range(n_uniform)
        )
    }
    out["layers"] = {
        "attn": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *(params[f"attn{i}"] for i in range(n_uniform)),
        ),
        "ff": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *(params[f"ff{i}"] for i in range(n_uniform)),
        ),
    }
    return out


class ProGen(nn.Module):
    config: ProGenConfig
    # physical mesh (jax.sharding.Mesh, hashable) — only consulted by the
    # explicit-collective attention path (config.use_ring_attn); the GSPMD
    # path needs no mesh on the model. Not serialized with the config.
    mesh: object = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, feed_to=None) -> jnp.ndarray:
        """tokens: (batch, seq_len) integer array. Returns float32 logits of
        shape (batch, seq_len, num_tokens).

        In config.decode mode the sequence axis is T >= 1 positions fed
        through the cache: the tokens of the block of T consecutive
        positions, aligned to a multiple of T, that holds the cache's
        position counter ``pos``. T must divide ``window_size`` (so no
        block straddles a window). Without ``feed_to`` every row is fed
        and ``pos`` must be at the block's start — trivially so at T = 1,
        the decode step. With ``feed_to`` (a traced scalar) the rows at
        positions ``pos <= p < feed_to`` are fed and the others are dead:
        computed, since shapes are static, but writing nothing to any
        cache leaf. ``pos`` advances by the number of rows fed."""
        c = self.config
        n = tokens.shape[-1]
        assert c.decode or feed_to is None, "feed_to is for decode mode"

        with jax.named_scope("head"):
            x = nn.Embed(
                c.num_tokens,
                c.dim,
                dtype=c.compute_dtype,
                param_dtype=c.params_dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.truncated_normal(stddev=0.02),
                    ("vocab", "embed"),
                ),
                name="embed",
            )(tokens)
            x = nn.with_logical_constraint(
                x, ("batch", "seq_act", "embed_act")
            )

        with jax.named_scope("project"):
            if c.decode:
                # full-length RoPE tables (blocks slice their rows), one
                # shared position counter advanced per call
                assert c.window_size % n == 0, (
                    f"a decode call feeds {n} positions, which must divide "
                    f"window_size={c.window_size}"
                )
                pos_var = self.variable(
                    "cache", "pos", lambda: jnp.zeros((), jnp.int32)
                )
                pos = pos_var.value
                at = (pos - pos % n if n > 1 else pos) + jnp.arange(n)
                rows = DecodeRows(
                    at,
                    None if feed_to is None else (at >= pos) & (at < feed_to),
                )
                sin, cos = fixed_pos_embedding(c.seq_len, c.dim_head)
            else:
                rows = None
                # RoPE tables are tiny; build in f32 once per trace
                # (progen.py:227)
                sin, cos = fixed_pos_embedding(n, c.dim_head)

        attn_cls, ff_cls = LocalAttentionBlock, FeedForwardBlock
        if c.remat and not c.decode:
            attn_cls = nn.remat(LocalAttentionBlock)
            ff_cls = nn.remat(FeedForwardBlock)

        n_uniform = c.depth - c.global_mlp_depth
        if c.scan_layers and not c.decode and n_uniform > 0:
            block_cls = nn.remat(UniformBlock) if c.remat else UniformBlock
            scan_cls = nn.scan(
                block_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=n_uniform,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            x, _ = scan_cls(c, glu=c.ff_glu, mesh=self.mesh, name="layers")(
                x, sin, cos
            )
            start = n_uniform
        else:
            start = 0

        for i in range(start, c.depth):
            use_gmlp = (c.depth - i) <= c.global_mlp_depth
            use_glu = (not use_gmlp) and c.ff_glu
            with jax.named_scope("project"):
                x = x + attn_cls(c, mesh=self.mesh, name=f"attn{i}")(
                    x, sin, cos, rows
                )
            with jax.named_scope("ffn"):
                x = x + ff_cls(
                    c, glu=use_glu, spatial_gate=use_gmlp, name=f"ff{i}"
                )(x, rows)
                x = nn.with_logical_constraint(
                    x, ("batch", "seq_act", "embed_act")
                )

        if c.decode and not self.is_initializing():
            with jax.named_scope("cache_write"):
                pos_var.value = pos + (
                    n if rows.live is None
                    else jnp.sum(rows.live.astype(jnp.int32))
                )

        with jax.named_scope("head"):
            x = ScaleNorm(
                c.layer_norm_epsilon, c.compute_dtype, c.params_dtype
            )(x)
            logits = nn.Dense(
                c.num_tokens,
                dtype=c.compute_dtype,
                param_dtype=c.params_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("vocab",)
                ),
                name="to_logits",
            )(x)
            return logits.astype(jnp.float32)

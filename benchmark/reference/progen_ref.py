"""Plain float32 reference of the ProGen forward pass (arXiv:2004.03497
architecture as lucidrains' progen_transformer builds it), independent of
``progen_tpu``: no cache, no kernels, no windows reshaped into batches —
attention is dense masked attention over absolute positions, computed in
query blocks so a long row fits.

Departures from the paper's description, all inherited from the code this
repo reproduces and therefore part of the semantics under test: token
shift on half the channels before every projection; RoPE (GPT-J
interleaved) applied to q, k AND v; queries in the first window see
``window`` phantom keys of score 0 and value 0 (the zero-padded previous
window); ``jax.nn.gelu``'s tanh approximation; scale-only LayerNorm.

Everything runs under ``default_matmul_precision("highest")`` — on a TPU a
float32 matmul is otherwise computed in bf16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MASK = -1e10


def _norm(x, scale, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def _shift(x):
    """First ceil(d/2) channels are delayed one position (zeros enter)."""
    split = x.shape[-1] - x.shape[-1] // 2
    delayed = jnp.pad(x[:-1, :split], ((1, 0), (0, 0)))
    return jnp.concatenate([delayed, x[:, split:]], axis=-1)


def _rope(x, pos):
    """x: (n, h, dh). Adjacent feature pairs rotate by pos * 10000^(-2i/dh)."""
    dh = x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(
        x.shape
    )


def _attend(q, k, v, window, q_block):
    """Dense masked attention. Key j is visible to query i iff j <= i and
    i's window index exceeds j's by at most 1. q, k, v: (n, h, dh)."""
    n, _, dh = q.shape
    outs = []
    for lo in range(0, n, q_block):
        hi = min(lo + q_block, n)
        k_lo = max(0, (lo // window - 1) * window)  # nothing earlier is visible
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(k_lo, hi)[None, :]
        visible = (j <= i) & (i // window - j // window <= 1)
        s = jnp.einsum("ihd,jhd->hij", q[lo:hi], k[k_lo:hi]) * dh ** -0.5
        s = jnp.where(visible[None], s, MASK)
        m = jnp.maximum(s.max(-1, keepdims=True), 0.0)
        e = jnp.exp(s - m)
        phantom = jnp.where(i < window, window * jnp.exp(-m), 0.0)
        p = e / (e.sum(-1, keepdims=True) + phantom)
        outs.append(jnp.einsum("hij,jhd->ihd", p, v[k_lo:hi]))
    return jnp.concatenate(outs, axis=0)


def _attn_block(x, p, cfg, q_block):
    n = x.shape[0]
    h, dh = cfg["heads"], cfg["dim_head"]
    y = _shift(_norm(x, p["ScaleNorm_0"]["norm"]["scale"],
                     cfg["layer_norm_epsilon"]))
    q, k, v = jnp.split(y @ p["to_qkv"]["kernel"], 3, axis=-1)
    pos = jnp.arange(n)
    q, k, v = (_rope(t.reshape(n, h, dh), pos) for t in (q, k, v))
    out = _attend(q, k, v, cfg["window_size"], q_block).reshape(n, h * dh)
    return out @ p["to_out"]["kernel"] + p["to_out"]["bias"]


def _ff_block(x, p, cfg, gmlp):
    n = x.shape[0]
    eps = cfg["layer_norm_epsilon"]
    y = _shift(_norm(x, p["ScaleNorm_0"]["norm"]["scale"], eps))
    y = y @ p["proj_in"]["kernel"] + p["proj_in"]["bias"]
    if gmlp:
        y = jax.nn.gelu(y, approximate=True)
        y, gate = jnp.split(y, 2, axis=-1)
        s = p["sgu"]
        gate = _norm(gate, s["ScaleNorm_0"]["norm"]["scale"], eps)
        # the (seq_len, seq_len) mix is causal, so a row shorter than
        # seq_len uses its top-left corner
        w = jnp.tril(s["spatial_weights"][:n, :n])
        y = y * (w @ gate + s["spatial_biases"][:n])
        y = y @ s["proj_out"]["kernel"] + s["proj_out"]["bias"]
    else:
        y, gate = jnp.split(y, 2, axis=-1)
        y = y * jax.nn.gelu(gate, approximate=True)
    return y @ p["proj_out"]["kernel"] + p["proj_out"]["bias"]


def forward(params, tokens, cfg, q_block=512):
    """tokens: (n,) ints, n <= seq_len. Returns float32 logits (n, vocab).
    ``params`` is the model's tree in either layout (uniform blocks stacked
    under 'layers', or unrolled attn{i}/ff{i}); leaves are upcast."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["embed"]["embedding"][tokens]
        depth, n_gmlp = cfg["depth"], cfg["global_mlp_depth"]
        n_uniform = depth - n_gmlp

        def uniform(x, layer):
            x = x + _attn_block(x, layer["attn"], cfg, q_block)
            return x + _ff_block(x, layer["ff"], cfg, False), None

        if "layers" in params:
            x, _ = jax.lax.scan(uniform, x, params["layers"])
        else:
            for i in range(n_uniform):
                x, _ = uniform(
                    x, {"attn": params[f"attn{i}"], "ff": params[f"ff{i}"]}
                )
        for i in range(n_uniform, depth):
            x = x + _attn_block(x, params[f"attn{i}"], cfg, q_block)
            x = x + _ff_block(x, params[f"ff{i}"], cfg, True)
        x = _norm(x, params["ScaleNorm_0"]["norm"]["scale"],
                  cfg["layer_norm_epsilon"])
        return x @ params["to_logits"]["kernel"] + params["to_logits"]["bias"]


def token_logprobs(params, row, cfg, q_block=512):
    """row: (n+1,) ints. log p(row[t+1] | row[:t+1]) for every t, float32."""
    logits = forward(params, row[:-1], cfg, q_block)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp, row[1:, None], axis=-1)[:, 0]


def row_loss(params, row, cfg, q_block=512):
    """The training loss of one row: mean of -log p over the non-pad
    targets plus the first pad (0 is pad and end-of-string)."""
    lp = token_logprobs(params, row, cfg, q_block)
    nonpad = row[1:] != 0
    keep = nonpad | (jnp.cumsum(~nonpad) == 1)
    return -(lp * keep).sum() / keep.sum()

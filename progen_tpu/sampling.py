"""Autoregressive sampling: top-k Gumbel-max decode.

Behavioral parity (/root/reference/progen_transformer/utils.py:97-135):
  * fixed-shape (length,) sequence buffer, scatter-write of each new token;
  * Gumbel-max top-k: ``mask = logits > min(top_k(logits))``, non-top-k
    logits AND their noise zeroed (utils.py:97-104) — quirk preserved: the
    zeroed entries still compete in the argmax at value 0, so a token
    outside the top-k can win if every top-k ``logit + gumbel`` lands below
    0. Kept for parity and because it is vanishingly rare with trained
    logits (document-don't-silently-fix). The beyond-reference
    temperature/top_p paths do NOT inherit it — tempering makes the
    all-kept-negative case common, so they mask with finfo.min;
  * ``add_bos`` shifts the prime right by one (utils.py:110-111);
  * post-hoc truncation: everything after the SECOND zero is zeroed (BOS is
    the first; the emitted EOS is the second, utils.py:132-133).

TPU-first design: the ENTIRE decode is one jitted ``lax.fori_loop`` — the
sequence buffer, params, and RNG key stay device-resident for the whole
generation. The reference instead runs a Python loop dispatching one jitted
full forward per token from the host (utils.py:115-129), paying a dispatch +
transfer round-trip per token. Still O(length) full forwards like the
reference; the incremental KV-cache path is tracked separately.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

EPS = 1e-20  # reference log() epsilon, utils.py:20


def gumbel_noise(key: jax.Array, shape) -> jnp.ndarray:
    u = jax.random.uniform(key, shape, minval=0.0, maxval=1.0)
    return -jnp.log(-jnp.log(u + EPS) + EPS)


def select_top_k(logits: jnp.ndarray, k: int):
    """(mask, masked_logits): keep entries strictly above the k-th value's
    minimum, zero the rest (utils.py:97-100)."""
    values, _ = jax.lax.top_k(logits, k)
    mask = logits > values.min(axis=-1, keepdims=True)
    return mask, jnp.where(mask, logits, 0.0)


def select_top_p(logits: jnp.ndarray, p) -> jnp.ndarray:
    """Nucleus mask over the last axis: the smallest set of
    highest-probability tokens whose cumulative softmax mass reaches ``p``
    (the crossing token included, so for p > 0 at least one survives).
    ``p`` may be a traced scalar; p >= 2.0 is the keep-all sentinel."""
    sort_idx = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < p  # mass BEFORE each token still short of p
    inv = jnp.argsort(sort_idx, axis=-1)
    return jnp.take_along_axis(keep_sorted, inv, axis=-1)


_TOP_P_OFF = 2.0  # select_top_p keep-all sentinel (any p >= 1 + max prob)


def _validate_knobs(temperature, top_p):
    """Range checks for the beyond-reference sampling knobs (raised from
    the public entry points, before any compile is paid)."""
    import math

    try:
        t = float(temperature)
    except (TypeError, ValueError):
        t = float("nan")
    if not (math.isfinite(t) and t > 0.0):
        raise ValueError(
            f"temperature must be a positive finite float, got {temperature}"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _knob_operands(temperature, top_p):
    """(parity, temperature_arr, top_p_arr): ``parity`` is the trace-time
    branch selector (defaults -> the exact reference quirk path); the float
    values ride as traced operands so sweeping them re-EXECUTES the same
    compiled decode instead of retracing it per value."""
    parity = temperature == 1.0 and top_p is None
    return (
        parity,
        jnp.float32(temperature),
        jnp.float32(_TOP_P_OFF if top_p is None else top_p),
    )


def _gumbel_topk_step(key, logit, top_k, parity=True, temperature=1.0,
                      top_p=_TOP_P_OFF):
    """One Gumbel-max draw (shared by both decode paths so the sampling
    quirks stay in lockstep). Returns (new_key, sampled_id).

    ``parity=True`` (the default-knobs path) reproduces the reference
    sampler bit-for-bit, INCLUDING its zeroing quirk: filtered tokens keep
    score 0 in the argmax (utils.py:106-135). With temperature/top_p
    engaged that quirk would be a real bug — dividing by a small
    temperature makes every kept score negative whenever the max logit is
    negative, so a zero-scored FILTERED token would win — hence the
    non-parity path masks with finfo.min instead. ``temperature``/``top_p``
    are traced scalars (top_p = 2.0 keeps all)."""
    key, sub = jax.random.split(key)
    noise = gumbel_noise(sub, logit.shape)
    if parity:
        if top_k is not None:
            mask, logit = select_top_k(logit, top_k)
            noise = noise * mask
        return key, jnp.argmax(logit + noise, axis=-1)
    logit = logit / temperature
    mask = select_top_p(logit, top_p)
    if top_k is not None:
        k_mask, _ = select_top_k(logit, top_k)
        mask = mask & k_mask
    logit = jnp.where(mask, logit, jnp.finfo(logit.dtype).min)
    return key, jnp.argmax(logit + noise, axis=-1)


def gumbel_step_dynamic(key, logit, top_k, parity, temperature, top_p):
    """One Gumbel-max draw with EVERY knob a traced operand — the serving
    engine's per-slot sampler. ``_gumbel_topk_step`` bakes top_k/parity in
    at trace time (right for one decode, one setting); a continuously
    batched engine holds requests with different settings in one compiled
    program, so here ``top_k`` (int32, 0 = off), ``parity`` (bool) and the
    float knobs all ride as data and both branches are computed then
    selected. Bit-identical to ``_gumbel_topk_step`` for every setting
    (pinned by tests/test_sampling.py::TestDynamicGumbelStep): the k-th
    value from a descending sort equals ``top_k(...).min()``, so the
    strict-> masks match float-for-float, and the knob branch re-derives
    its threshold from the TEMPERED logits exactly as select_top_k does
    (dividing the untempered threshold could round differently).
    Vmappable; returns (new_key, sampled_id)."""
    key, sub = jax.random.split(key)
    noise = gumbel_noise(sub, logit.shape)
    v = logit.shape[-1]
    kc = jnp.clip(top_k, 1, v) - 1
    k_on = top_k > 0

    # reference-parity branch (zeroing quirk preserved, as in the static
    # sampler's parity path; top_k off => no masking at all)
    kth = jax.lax.dynamic_index_in_dim(
        -jnp.sort(-logit, axis=-1), kc, axis=-1, keepdims=False
    )
    mask_p = (logit > kth) | ~k_on
    pick_parity = jnp.argmax(
        jnp.where(mask_p, logit, 0.0) + jnp.where(mask_p, noise, 0.0),
        axis=-1,
    )

    # knob branch (finfo.min masking — see _gumbel_topk_step's rationale)
    lt = logit / temperature
    kth_t = jax.lax.dynamic_index_in_dim(
        -jnp.sort(-lt, axis=-1), kc, axis=-1, keepdims=False
    )
    mask = select_top_p(lt, top_p) & ((lt > kth_t) | ~k_on)
    pick_knobs = jnp.argmax(
        jnp.where(mask, lt, jnp.finfo(lt.dtype).min) + noise, axis=-1
    )
    return key, jnp.where(parity, pick_parity, pick_knobs)


# Largest top_k whose threshold comes from a partial selection; beyond it
# (and for every vocabulary no wider) the row is sorted whole. Either way
# the threshold is the same element of the row, so the draw is too.
_TOP_K_PARTIAL = 64


def _kth_largest(x, kc):
    """The ``kc``-th largest (zero-based, (S,)) of each row of x (S, V)."""

    def by_sort():
        return jnp.take_along_axis(-jnp.sort(-x, axis=-1), kc[:, None], 1)[:, 0]

    if x.shape[-1] <= _TOP_K_PARTIAL:
        return by_sort()

    def by_top_k():
        top, _ = jax.lax.top_k(x, _TOP_K_PARTIAL)
        at = jnp.minimum(kc, _TOP_K_PARTIAL - 1)
        return jnp.take_along_axis(top, at[:, None], 1)[:, 0]

    return jax.lax.cond(jnp.all(kc < _TOP_K_PARTIAL), by_top_k, by_sort)


def gumbel_step_slots(keys, logits, top_k, parity, temperature, top_p, live):
    """``gumbel_step_dynamic`` for all slots of a pool at once, row for
    row the same draw bit for bit (tests/test_sampling.py), arranged for a
    vocabulary of 10^5: the top-k threshold is read off a partial
    selection while every slot's k allows, and the knob branch — whose
    nucleus needs the whole row sorted — runs only in a step where a live
    slot asks for it. keys (S, ...), logits (S, V); the rest (S,)."""
    split = jax.vmap(jax.random.split)(keys)
    keys, subs = split[:, 0], split[:, 1]
    v = logits.shape[-1]
    noise = jax.vmap(lambda k: gumbel_noise(k, (v,)))(subs)
    kc = jnp.clip(top_k, 1, v) - 1
    k_off = (top_k <= 0)[:, None]

    mask_p = (logits > _kth_largest(logits, kc)[:, None]) | k_off
    pick_parity = jnp.argmax(
        jnp.where(mask_p, logits, 0.0) + jnp.where(mask_p, noise, 0.0),
        axis=-1,
    )

    def with_knobs():
        lt = logits / temperature[:, None]
        mask = jax.vmap(select_top_p)(lt, top_p) & (
            (lt > _kth_largest(lt, kc)[:, None]) | k_off
        )
        pick = jnp.argmax(
            jnp.where(mask, lt, jnp.finfo(lt.dtype).min) + noise, axis=-1
        )
        return jnp.where(parity, pick_parity, pick)

    return keys, jax.lax.cond(
        jnp.all(parity | ~live), lambda: pick_parity, with_knobs
    )


def _validate_infill(template, frozen, length, num_tokens):
    """Host-side checks for the fixed-position infilling mask pair
    (the constrained-sampling workload, progen_tpu/workloads/infill.py).
    Returns (template, frozen) as device-ready (length,) arrays, or
    (None, None) when infilling is off. ``template`` pins token ids at
    positions where ``frozen`` is True; free positions sample normally."""
    if (template is None) != (frozen is None):
        raise ValueError("template and frozen must be given together")
    if template is None:
        return None, None
    t = np.asarray(template, np.int32).reshape(-1)
    f = np.asarray(frozen, bool).reshape(-1)
    if t.shape[0] != length or f.shape[0] != length:
        raise ValueError(
            f"template/frozen must be (length={length},) arrays, got "
            f"{t.shape} / {f.shape}"
        )
    if (t < 0).any() or (t >= num_tokens).any():
        raise ValueError(
            f"template token ids must be in [0, {num_tokens})"
        )
    if ((t == 0) & f).any():
        raise ValueError(
            "frozen positions must pin a nonzero token id (0 is the "
            "BOS/EOS/pad token — freezing it would end the sequence)"
        )
    return jnp.asarray(t), jnp.asarray(f)


def _constrain(sampled, logit, pos, template, frozen):
    """Apply the infill mask to one draw at write position ``pos``:
    frozen positions take the template token verbatim; at free positions
    a drawn EOS (0) is replaced by the best non-EOS token, because an
    infill template has a fixed extent and an early EOS would abort the
    fill. Both overrides are gated on the mask actually freezing
    something (``frozen.any()``), so an all-free mask is bit-identical
    to unconstrained sampling under the same key — the draw itself
    always happens, keeping the one-split-per-token PRNG contract (and
    journal replay) unchanged. ``logit``/``sampled`` may carry a leading
    batch axis; ``pos`` is a traced scalar."""
    alt = (jnp.argmax(logit[..., 1:], axis=-1) + 1).astype(sampled.dtype)
    infill_on = jnp.any(frozen, axis=-1)
    sampled = jnp.where(infill_on & (sampled == 0), alt, sampled)
    frz = jnp.take(frozen, pos, axis=-1)
    tpl = jnp.take(template, pos, axis=-1).astype(sampled.dtype)
    return jnp.where(frz, tpl, sampled)


def _prepare_seq(model, prime, length, add_bos):
    """Validate and build the fixed-shape decode buffer (shared by ALL
    decode paths): BOS shift (utils.py:110-111), right-padding, and the
    bounds the model can actually serve. ``prime`` may be (prime_len,) or
    (batch, prime_len) — padding applies to the last axis either way.
    The buffer is built with numpy on the host: the serving loop calls
    this at every submit and admission, and a pad on the device would be
    a dispatch, a compile per new shape and a read-back that waits
    behind whatever the device is running."""
    seq_len = model.config.seq_len
    if length > seq_len:
        raise ValueError(
            f"length {length} exceeds the model's seq_len {seq_len} (RoPE "
            f"tables and the SGU spatial matrix are bound to seq_len)"
        )
    prime = np.asarray(prime, np.int32)
    start = prime.shape[-1] + (1 if add_bos else 0)
    if start == 0:
        raise ValueError("empty prime requires add_bos=True")
    if start >= length:
        raise ValueError(f"prime length {start} must be < length {length}")
    pad = (
        (1, length - prime.shape[-1] - 1)
        if add_bos
        else (0, length - prime.shape[-1])
    )
    widths = ((0, 0),) * (prime.ndim - 1) + (pad,)
    return np.pad(prime, widths), start


@functools.partial(
    jax.jit,
    static_argnames=("model", "length", "top_k", "parity"),
)
def _decode(
    model,
    params,
    key: jax.Array,
    seq: jnp.ndarray,
    start_pos: jnp.ndarray,
    length: int,
    top_k: Optional[int],
    parity: bool = True,
    temperature: jnp.ndarray = 1.0,
    top_p: jnp.ndarray = _TOP_P_OFF,
    template=None,
    frozen=None,
):
    """seq: (length,) int32 buffer primed up to start_pos. One fori_loop
    iteration = one full forward + one Gumbel top-k draw + one scatter.
    ``template``/``frozen`` (both (length,) or None) are the infilling
    constraint — see _constrain."""

    def body(pos, carry):
        seq, key = carry
        logits = model.apply({"params": params}, seq[None])[0]
        logit = jax.lax.dynamic_index_in_dim(
            logits, pos - 1, axis=0, keepdims=False
        )
        key, sampled = _gumbel_topk_step(
            key, logit, top_k, parity, temperature, top_p
        )
        if template is not None:
            sampled = _constrain(sampled, logit, pos, template, frozen)
        seq = jax.lax.dynamic_update_index_in_dim(
            seq, sampled.astype(seq.dtype), pos, axis=0
        )
        return seq, key

    seq, _ = jax.lax.fori_loop(start_pos, length, body, (seq, key))
    # zero everything after the second zero token (utils.py:132-133)
    after_eos = jnp.cumsum(seq == 0, axis=-1) > 1
    return seq * (~after_eos)


def sample(
    key: jax.Array,
    model,
    params,
    prime: jnp.ndarray,
    length: int,
    top_k: Optional[int] = 25,
    add_bos: bool = False,
    temperature: float = 1.0,
    top_p: Optional[float] = None,
    template=None,
    frozen=None,
) -> jnp.ndarray:
    """Generate a (length,) token sequence continuing ``prime`` (1-D ints).

    Defaults mirror sample.py:70 (top_k=25; train-loop sampling uses
    add_bos=True, train.py:218). ``temperature``/``top_p`` are
    beyond-reference knobs; defaults are exact parity.
    ``template``/``frozen`` ((length,) arrays) enable fixed-position
    infilling: frozen positions emit the template token verbatim, free
    positions sample normally (progen_tpu/workloads/infill.py builds the
    pair from a template string).
    """
    _validate_knobs(temperature, top_p)
    parity, t_arr, p_arr = _knob_operands(temperature, top_p)
    seq, start = _prepare_seq(model, prime, length, add_bos)
    template, frozen = _validate_infill(
        template, frozen, length, model.config.num_tokens
    )
    return _decode(
        model, params, key, seq, jnp.asarray(start), length, top_k,
        parity, t_arr, p_arr, template, frozen,
    )


def sample_batched(
    key: jax.Array,
    model,
    params,
    primes: jnp.ndarray,
    length: int,
    top_k: Optional[int] = 25,
    add_bos: bool = False,
    temperature: float = 1.0,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """Batched decode: ``primes`` (batch, prime_len) -> (batch, length).

    Each row draws its own Gumbel stream (independent fold of ``key``);
    row i equals ``sample(fold_in(key, i), ...)`` on that prime. The
    reference is single-sequence only (utils.py:106) — batching the decode
    keeps the MXU busy on a mesh instead of wasting it on batch-1 matmuls.
    """
    _validate_knobs(temperature, top_p)
    parity, t_arr, p_arr = _knob_operands(temperature, top_p)
    primes, batch, keys = _batched_primes_and_keys(key, primes)
    seqs, start = _prepare_seq(model, primes, length, add_bos)
    return jax.vmap(
        lambda k, s: _decode(
            model, params, k, s, jnp.asarray(start), length, top_k,
            parity, t_arr, p_arr,
        )
    )(keys, seqs)


def _batched_primes_and_keys(key, primes):
    """Shared batched-decode prep: validate (batch, prime_len) primes and
    derive one independent Gumbel stream per row (fold of ``key``) — the
    single source of the 'row i == single decode with fold_in(key, i)'
    contract both batched decoders document."""
    primes = jnp.asarray(primes, jnp.int32)
    if primes.ndim != 2 or primes.shape[0] == 0:
        raise ValueError(
            f"primes must be (batch >= 1, prime_len), got {primes.shape}"
        )
    batch = primes.shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(batch))
    return primes, batch, keys


@functools.lru_cache(maxsize=8)
def _cache_init_fn(model, sharding, batch: int = 1):
    """Compiled zeroed-cache builder, cached on (model, sharding) so a
    train loop's cadenced samples re-EXECUTE it (fresh cache arrays) without
    re-TRACING it every cadence. ``sharding`` is the params' mesh sharding,
    replicated: in multi-process runs a bare jit would commit the cache to
    each process's local device, which cannot be mixed with globally-sharded
    params inside the decode loop (incompatible-devices error at the
    first cadenced sample). Shardings and flax modules both hash by value,
    so the cache key is stable across calls."""
    out_shardings = None
    if sharding is not None and getattr(sharding, "mesh", None) is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        out_shardings = NamedSharding(sharding.mesh, PartitionSpec())
    # progen: ignore[PGL004] — the fresh lambda is jitted at most once per
    # (model, batch, sharding) tuple: the enclosing lru_cache is the cache
    return jax.jit(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32)
        )["cache"],
        out_shardings=out_shardings,
    )


def sample_fast(
    key: jax.Array,
    model,
    params,
    prime: jnp.ndarray,
    length: int,
    top_k: Optional[int] = 25,
    add_bos: bool = False,
    temperature: float = 1.0,
    top_p: Optional[float] = None,
    template=None,
    frozen=None,
) -> jnp.ndarray:
    """KV-cache decode: O(2w·d) attention per emitted token via the model's
    config.decode mode (rolling two-window ring buffer + token-shift states
    + SGU gate history) instead of the naive path's full forward per token.
    Same sampling semantics as `sample` (including ``template``/``frozen``
    infilling)."""
    # validate before the (comparatively) expensive cache-init compile
    seq, start = _prepare_seq(model, prime, length, add_bos)
    template, frozen = _validate_infill(
        template, frozen, length, model.config.num_tokens
    )
    dec_model, params, cache = _decode_setup(model, params, batch=1)
    # the single decode IS the batched kernel at B=1 (row key = the raw
    # key, preserving this function's historical stream); vmapped PRNG
    # draws are bitwise equal to unbatched ones, which the batched-row
    # parity tests pin empirically
    _validate_knobs(temperature, top_p)
    parity, t_arr, p_arr = _knob_operands(temperature, top_p)
    out = _decode_incremental_batched(
        dec_model, params, cache, key[None], seq[None],
        jnp.asarray(start), length, top_k, parity, t_arr, p_arr,
        None if template is None else template[None],
        None if frozen is None else frozen[None],
    )
    return out[0]


def _decode_setup(model, params, batch: int, max_len=None):
    """(decode model, decode-layout params, fresh zeroed cache) for the
    KV-cache paths; the cache is ``cache_builder``'s. ``max_len`` bounds
    the cache of a family whose state grows with the sequence
    (``models.decode_model``)."""
    from progen_tpu.models import decode_model, unstack_params

    dec_model = decode_model(model, max_len)
    # decode mode is always unrolled (per-layer caches); a scanned stacked
    # layout is converted, any other comes back as it is
    params = unstack_params(params, model.config)
    return dec_model, params, cache_builder(dec_model, params, batch)()


def cache_builder(dec_model, params, batch: int):
    """The compiled builder of a decode model's initial cache: every call
    returns a new tree. It comes from a trace-cached jitted init (params
    creation inside init is dead-code-eliminated since only the cache
    collection is returned), replicated on the params' mesh — see
    _cache_init_fn."""
    param_leaf = next(
        (leaf for leaf in jax.tree.leaves(params) if isinstance(leaf, jax.Array)),
        None,
    )
    sharding = param_leaf.sharding if param_leaf is not None else None
    try:
        return _cache_init_fn(dec_model, sharding, batch)
    except TypeError:  # unhashable sharding: fall back to uncached
        return _cache_init_fn.__wrapped__(dec_model, sharding, batch)


# Rows of one prefill block, at most: a prompt goes through the cache in
# blocks of this many positions per pass over the weights. On a v5e a
# pass is bound by reading the weights up to about 240 rows (197 TFLOP/s
# over 819 GB/s, two bytes a weight). Chip readings on ProGen-large
# (PERF.md, PR 27): a block costs 4.8 ms at 64 rows and 5.2 ms at 128, a
# 16-token chunk 16.1 ms at 16, 32 and 64 rows and 16.3 ms at 128.
_FEED_ROWS = 128


def feed_width(config) -> int:
    """The prefill block width for a model: what its family states
    (``config.feed_rows``), else the largest divisor of ProGen's
    ``window_size`` that is at most ``_FEED_ROWS``. Dividing the window
    is what lets a block write its keys before attending (no block
    straddles a window boundary, see ``_decode_attend``)."""
    rows = getattr(config, "feed_rows", None)
    if rows:  # a family without windows names its own block
        return int(rows)
    w = config.window_size
    return max(d for d in range(1, min(w, _FEED_ROWS) + 1) if w % d == 0)


def feed_block_count(width: int, lo: int, hi: int) -> int:
    """Blocks ``feed_tokens`` runs for positions ``[lo, hi)``: the
    aligned blocks of ``width`` that the range touches (host arithmetic,
    the same the loop bounds below do on the device)."""
    return -(-hi // width) - lo // width if hi > lo else 0


def _row_major(x):
    """``x`` held to the row-major layout wherever the compiler would
    choose one for it (inside a loop or a branch)."""
    if x.ndim < 2:
        return x
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim)))
    )


def feed_tokens(model, params, cache, tokens, lo, hi):
    """Feed positions ``[lo, hi)`` of ``tokens`` ((B, L); the cache's
    ``pos`` must stand at ``lo``) through a decode cache in blocks of
    ``feed_width`` positions per ``model.apply``: one pass over the
    weights per block, not per token. The one prime feed of every
    cached decoder — the serving engine's chunk program
    and ``sample_fast`` / ``sample_fast_batched`` — which is
    what keeps their streams token-identical.

    Blocks are aligned to absolute positions, so a position sits in the
    same row of the same block however the prompt is split into calls,
    reads the same cache rows and reduces over them in the same order:
    the cache after ``[0, hi)`` is bit-equal under every split. Rows of
    a block outside ``[lo, hi)`` are dead (``ProGen.__call__``).
    ``lo``/``hi`` are traced loop bounds, so ONE compiled program serves
    every chunk size and resume depth.

    A block hands every cache leaf on in the layout it came in (row-major,
    the layout a program's arguments and results have). Left to itself
    the TPU compiler carries ProGen's K/V rings through the loop with
    their rows innermost, which costs a conversion of every ring before
    the loop and another after it, however few rows the blocks write
    (ProGen-large on a v5e: 94 copies of 3.67 MB, 1.4 of a 16-token
    chunk's 5.6 ms; PERF.md, PR 37). No value changes."""
    if getattr(model, "slot_batched", False):
        # positions are an argument of that family's decode mode, not a
        # counter in its cache: it runs the same aligned blocks itself
        return model.feed_tokens(params, cache, tokens, lo, hi)
    t = feed_width(model.config)
    last = tokens.shape[-1] - 1

    def feed(blk, cache):
        # rows past the buffer's end are dead: any token will do there
        with jax.named_scope("head"):  # the block's tokens to embed
            at = jnp.minimum(blk * t + jnp.arange(t), last)
            fed = tokens[:, at]
        _, mut = model.apply(
            {"params": params, "cache": cache}, fed, hi, mutable=["cache"],
        )
        with jax.named_scope("cache_write"):
            return jax.tree.map(_row_major, mut["cache"])

    return jax.lax.fori_loop(
        lo // t, jnp.where(hi > lo, -(-hi // t), lo // t), feed, cache
    )


@functools.partial(
    jax.jit,
    static_argnames=("model", "length", "top_k", "parity"),
)
def _decode_incremental_batched(
    model, params, cache, keys, seqs, start_pos, length, top_k,
    parity=True, temperature=1.0, top_p=_TOP_P_OFF,
    template=None, frozen=None,
):
    """Batched KV-cache decode: seqs (B, length), keys (B,) — one
    independent Gumbel stream per row, caches carry a leading batch axis
    (they are built batch-shaped by the model's decode variables).
    ``template``/``frozen`` (both (B, length) or None) apply the infill
    constraint per row — see _constrain."""

    def feed(seqs, p, cache):
        tok = jax.lax.dynamic_slice_in_dim(seqs, p, 1, axis=1)  # (B, 1)
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok, mutable=["cache"]
        )
        return logits[:, 0], mut["cache"]  # (B, vocab)

    cache = feed_tokens(model, params, cache, seqs, 0, start_pos - 1)

    draw = jax.vmap(
        lambda k, l: _gumbel_topk_step(
            k, l, top_k, parity, temperature, top_p
        )
    )

    def gen(p, carry):
        seqs, cache, keys = carry
        logit, cache = feed(seqs, p, cache)
        keys, sampled = draw(keys, logit)
        if template is not None:
            sampled = _constrain(sampled, logit, p + 1, template, frozen)
        seqs = jax.lax.dynamic_update_slice(
            seqs, sampled[:, None].astype(seqs.dtype), (0, p + 1)
        )
        return seqs, cache, keys

    seqs, _, _ = jax.lax.fori_loop(
        start_pos - 1, length - 1, gen, (seqs, cache, keys)
    )
    after_eos = jnp.cumsum(seqs == 0, axis=-1) > 1
    return seqs * (~after_eos)


def sample_fast_batched(
    key: jax.Array,
    model,
    params,
    primes: jnp.ndarray,
    length: int,
    top_k: Optional[int] = 25,
    add_bos: bool = False,
    temperature: float = 1.0,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """Batched KV-cache decode: ``primes`` (batch, prime_len) ->
    (batch, length), O(B·2w·d) attention per emitted step. Row i is
    BIT-IDENTICAL to ``sample_fast(fold_in(key, i), ...)`` on that prime
    (and therefore to ``sample_batched``'s row i) — same per-row Gumbel
    streams, decoded together so the MXU sees batched matmuls instead of
    batch-1 throwaway work."""
    _validate_knobs(temperature, top_p)
    parity, t_arr, p_arr = _knob_operands(temperature, top_p)
    primes, batch, keys = _batched_primes_and_keys(key, primes)
    seqs, start = _prepare_seq(model, primes, length, add_bos)
    dec_model, params, cache = _decode_setup(model, params, batch=batch)
    return _decode_incremental_batched(
        dec_model, params, cache, keys, seqs, jnp.asarray(start), length,
        top_k, parity, t_arr, p_arr,
    )

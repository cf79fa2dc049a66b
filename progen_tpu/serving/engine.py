"""Slot-pool continuous-batching engine over the incremental decoder.

The single-shot decoders in sampling.py compile one program per (batch,
length) and run it to completion — fine for a training cadence, wasteful
for serving, where requests arrive and finish at different times. This
engine keeps a fixed pool of ``max_slots`` decode lanes resident on the
device (the slot-pool idea of vLLM/PagedAttention, SOSP '23, at
granularity one-slot-one-request) and advances EVERY live lane one token
per ``decode_step`` call (the iteration-level scheduling of Orca,
OSDI '22). All shapes are functions of (max_slots, max_len) only, so an
engine's whole lifetime re-executes exactly three compiled programs:
``_prefill_chunk`` (a slice of a prompt through a batch-1 cache — the
whole prompt when admission is unbudgeted), ``_prefill_finish`` (that
cache and the request's state into the pool) and ``_decode_step``. Each
takes the state it rewrites DONATED — the pool, and the batch-1 cache
of the admission in flight, which the engine owns from ``begin_prefill``
to the slot's activation (see ``PendingPrefill``) — and nothing else of
an admission runs on the device: its scalars ride as host values. The
two that read weights take ``ServeEngine.served_params``: for a float
engine the tree with every leaf the model would convert at each use held
in the compute type, for an int8 engine the quantized pair
(``ops.quant.QuantizedParams``), which the programs dequantize on the
device. One set of programs, whatever the engine was handed. Their
device ops are filed under the mechanism classes of
``telemetry/scopes.py``: the model files its own, the decode step's draw
and bookkeeping after the logits are ``sample``, and ``_prefill_finish``
scatters the cache under ``cache_write`` and the slot's state under
``sample``.

A prompt goes through a batch-1 cache in BLOCKS of positions
(``sampling.feed_tokens``): one ``model.apply`` of ``prefill_width``
rows — the largest divisor of the model's ``window_size`` up to 128 — is
one pass over the weights, so a chunk of admission costs about the same
whether it carries one token or a whole block. Blocks are aligned to
absolute positions: however a prompt is split (chunk budget, prefix-cache
resume depth, journal replay), every position is computed in the same
row of the same block, and the primed cache is bit-equal.

Per-slot positions without touching the model: decode mode keeps a
single scalar ``pos`` cache counter (progen.py), which a batch-B cache
shares across rows — useless when rows start and finish at different
times. Instead the pool stacks ``max_slots`` BATCH-1 cache trees along
a leading slot axis and the decode step ``vmap``s the one-token apply
over it, so every slot carries its own scalar ``pos`` (and its own ring
indices, shift states, and gate history). Dead slots keep computing —
static shapes are the point — on garbage caches; that is safe because
an admission rewrites the slot's entire cache tree from one that began
as the model's own initial cache (NOT zeros: ``slot_pos`` initialises
to -1) before the slot is ever read again.

Sampling params ride as per-slot DATA (gumbel_step_dynamic), so one
compiled step serves any mix of temperature/top_k/top_p. Each slot
follows the standalone per-request PRNG stream: a request decoded here
is bit-identical to ``sample_fast(key=request_key, ...)`` — pinned by
tests/test_serving.py.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.models.layers import row_write_paths
from progen_tpu.ops.pallas_decode_attention import kernel_block, listed_rows
from progen_tpu.ops.quant import QuantizedParams, dequantized, quantize_tree
from progen_tpu.sampling import (
    _TOP_P_OFF,
    _decode_setup,
    _prepare_seq,
    _validate_infill,
    _validate_knobs,
    cache_builder,
    feed_block_count,
    feed_tokens,
    feed_width,
    gumbel_step_dynamic,
    gumbel_step_slots,
)
from progen_tpu.serving.served_tree import promoted_mask, serve_tree
from progen_tpu.telemetry.spans import span as _span, stage as _stage

logger = logging.getLogger(__name__)


class PreparedParams(NamedTuple):
    """A checkpoint transformed and verified for hot swap by
    ``ServeEngine.prepare_params`` (background-thread safe), waiting for
    ``commit_params`` (loop thread, between decode steps)."""

    params: dict
    quant_report: Optional[dict] = None  # int8 engines: the calibration
    served: Any = None  # ``params`` as the programs take them


class SlotBatch(NamedTuple):
    """Device-resident pooled state; every leaf's leading axis is
    ``max_slots``. A pytree, so it moves through jit/vmap whole."""

    cache: dict  # model cache, leaves (S, *batch1_leaf_shape)
    seqs: jnp.ndarray  # (S, L) int32 token buffers (right-padded with 0)
    cur: jnp.ndarray  # (S,) int32 position of the last written token
    keys: jnp.ndarray  # (S, ...) per-slot PRNG keys
    nz: jnp.ndarray  # (S,) int32 zero-token count (BOS first, EOS second)
    target: jnp.ndarray  # (S,) int32 requested total length
    temp: jnp.ndarray  # (S,) f32 temperature
    top_p: jnp.ndarray  # (S,) f32 nucleus mass (_TOP_P_OFF = off)
    top_k: jnp.ndarray  # (S,) int32 (0 = off)
    parity: jnp.ndarray  # (S,) bool reference-quirk sampling branch
    live: jnp.ndarray  # (S,) bool slot is decoding
    template: jnp.ndarray  # (S, L) int32 infill template (all-0 = off)
    frozen: jnp.ndarray  # (S, L) bool infill frozen-position mask


def _scatter_slot(
    slots: SlotBatch,
    cache1,
    slot,
    tokens,
    start,
    target,
    key,
    temp,
    top_p,
    top_k,
    parity,
    template,
    frozen,
):
    """Scatter a fully primed batch-1 cache + all per-slot state into
    the pool and mark ``slot`` live. Pure data movement (no model
    arithmetic): the body of ``_prefill_finish``. The cache's scatter is
    ``cache_write``, the slot's state ``sample`` (its bookkeeping)."""
    length = slots.seqs.shape[1]
    with jax.named_scope("cache_write"):
        cache = jax.tree.map(
            lambda pool, c: jax.lax.dynamic_update_index_in_dim(
                pool, c, slot, axis=0
            ),
            slots.cache,
            cache1,
        )
    with jax.named_scope("sample"):
        # zeros already present in the primed region count toward the
        # stop-at-second-zero rule (same cumsum the standalone decoders
        # apply)
        nz0 = jnp.sum(
            ((tokens == 0) & (jnp.arange(length) < start)).astype(jnp.int32)
        )
        return SlotBatch(
            cache=cache,
            seqs=jax.lax.dynamic_update_index_in_dim(
                slots.seqs, tokens, slot, axis=0
            ),
            cur=slots.cur.at[slot].set(start - 1),
            keys=slots.keys.at[slot].set(key),
            nz=slots.nz.at[slot].set(nz0),
            target=slots.target.at[slot].set(target),
            temp=slots.temp.at[slot].set(temp),
            top_p=slots.top_p.at[slot].set(top_p),
            top_k=slots.top_k.at[slot].set(top_k),
            parity=slots.parity.at[slot].set(parity),
            live=slots.live.at[slot].set(True),
            template=jax.lax.dynamic_update_index_in_dim(
                slots.template, template, slot, axis=0
            ),
            frozen=jax.lax.dynamic_update_index_in_dim(
                slots.frozen, frozen, slot, axis=0
            ),
        )


@functools.partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,)
)
def _prefill_chunk(model, params, cache, tokens, lo, hi):
    """One budgeted slice of a prefill: feed ``tokens[lo:hi]`` through an
    in-progress batch-1 cache, a block of positions per pass over the
    weights (a chunk inside one aligned block is one pass, whatever its
    token count; an unbudgeted admission is the whole prime as its one
    chunk). ``lo``/``hi`` are TRACED, so one compiled program serves
    every chunk size and resume depth (a prefix-cache hit resumes at an
    arbitrary ``lo``, mid-block: the rows before it are dead).
    ``params`` is ``ServeEngine.served_params``: a float tree, or the
    quantized pair of an int8 engine, dequantized here (XLA fuses
    convert+scale into each consuming matmul). The cache (arg 2) is
    DONATED: a chunk writes its rows into the buffers it was handed and
    the call makes no buffer (ProGen-large on a v5e, the pool of 32
    slots resident: 123 leaves, 206 MB, whose allocation was 7.6 of the
    9.7 ms the undonated call took to enqueue; PERF.md, PR 37). Whoever
    calls owns the tree it hands in and must not read it again: the
    engine feeds a tree that is the admission's alone
    (``PendingPrefill``), never one the prefix cache holds."""
    params = dequantized(params, model.config.compute_dtype)
    return feed_tokens(model, params, cache, tokens[None], lo, hi)


@functools.partial(
    jax.jit, static_argnames=("new_cache",), donate_argnums=(0, 1)
)
def _prefill_finish(slots, cache1, slot, tokens, start, target, key,
                    temp, top_p, top_k, parity, template, frozen, *,
                    new_cache):
    """Final step of a prefill: scatter the fully primed cache +
    per-slot state into the pool (the ONLY point an admission touches
    the pool — mid-chunk state lives outside it, so decode steps
    between chunks never see a half-primed slot). Both trees are
    DONATED. The pool (``slots``): every leaf is rebuilt and the caller
    immediately rebinds ``self.slots`` to the result, so the old buffers
    alias the new ones instead of doubling the pool's HBM footprint.
    The batch-1 cache (``cache1``): once scattered nobody reads it
    again, so its buffers come back holding the model's INITIAL cache —
    ``new_cache`` is the compiled builder of that tree
    (``sampling.cache_builder``; static, and inlined here from the trace
    it already has), whose values are constants, written over the spent
    ones — and the next cold admission starts from them: an admission
    allocates nothing (run as a program of its own the builder makes 123
    buffers and takes 6.2 ms to enqueue where this call takes 3.3;
    PERF.md, PR 37). Returns ``(pool, that tree)``. No model arithmetic
    and no weights."""
    pool = _scatter_slot(slots, cache1, slot, tokens, start, target, key,
                         temp, top_p, top_k, parity, template, frozen)
    with jax.named_scope("cache_write"):
        return pool, new_cache()


@jax.jit
def _copy_cache(cache):
    """A second batch-1 tree with the first one's values, for the two
    moments at which one tree would otherwise have two owners, the
    prefix cache and an admission (``begin_prefill``'s hit,
    ``advance_prefill``'s insert): one keeps the original, the other
    takes this."""
    return jax.tree.map(jnp.copy, cache)


def _decode_step_impl(model, params, slots: SlotBatch):
    """Advance ALL slots one token: vmapped batch-1 apply over the slot
    axis, per-slot dynamic Gumbel draw, masked scatter-back. Dead slots
    compute too (their writes are masked out) — the price of a single
    static-shape program, and exactly what keeps a TPU from recompiling
    as traffic churns. Returns (new_slots, sampled, was_live, finished);
    ``finished`` flags slots that JUST hit EOS (second zero) or their
    requested length this step. Un-jitted body of ``_decode_step``
    (tests/test_served_tree.py traces it on its own). The ``vmap`` over
    the slots is also what picks the attention of a ProGen step: on a
    TPU each attention layer is one kernel over the pool that reads
    every slot's LIVE ring blocks only
    (``ops/pallas_decode_attention.py``: the blocks a slot's query sees,
    listed from the cache's own ``slot_pos``; dead slots read what their
    counters say, like any other); off the TPU, or where a model's head
    size or window does not fit the kernel's tiling, it is the plain
    form over whole rings. ``ServeEngine._count_ring_rows`` keeps the
    host's account of it."""
    n_slots, length = slots.seqs.shape
    pos = jnp.clip(slots.cur, 0, length - 1)
    if getattr(model, "slot_batched", False):
        return _decode_step_batched(model, params, slots, pos)
    toks = jnp.take_along_axis(slots.seqs, pos[:, None], axis=1)[:, :, None]

    def one(cache, tok):
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok, mutable=["cache"]
        )
        return logits[0, 0], mut["cache"]

    logits, cache = jax.vmap(one)(slots.cache, toks)
    with jax.named_scope("sample"):
        keys, sampled = jax.vmap(gumbel_step_dynamic)(
            slots.keys, logits, slots.top_k, slots.parity, slots.temp,
            slots.top_p,
        )
        return _write_sampled(slots, cache, logits, keys, sampled)


def _decode_step_batched(model, params, slots: SlotBatch, pos):
    """The decode step of a family whose layers must see every live slot
    at once (routed experts: one grouped product reads each touched
    expert once, where a vmapped batch-1 apply would gather each slot's
    own): the slots are one batch with a position a row, the draw is the
    pool-wide twin of the per-slot sampler, and the family's counts ride
    behind the sampled tokens, in a read the host already makes (an
    output of their own would be a fourth device-to-host read a step);
    ``model.fold_counts`` names them on the host."""
    toks = jnp.take_along_axis(slots.seqs, pos[:, None], axis=1)[:, 0]
    logits, cache, counts = model.decode_slots(
        params, slots.cache, toks, pos, slots.live
    )
    with jax.named_scope("sample"):
        keys, sampled = gumbel_step_slots(
            slots.keys, logits, slots.top_k, slots.parity, slots.temp,
            slots.top_p, slots.live,
        )
        new, sampled, live, finished = _write_sampled(
            slots, cache, logits, keys, sampled
        )
        counts = counts.astype(sampled.dtype)
        return new, jnp.concatenate([sampled, counts]), live, finished


def _write_sampled(slots: SlotBatch, cache, logits, keys, sampled):
    """The part of a decode step every family shares: the infill rule,
    the masked scatter-back, the stop rule."""
    n_slots, length = slots.seqs.shape
    sampled = sampled.astype(slots.seqs.dtype)
    wpos = jnp.clip(slots.cur + 1, 0, length - 1)
    # infilling (mirrors sampling.py::_constrain so an infilled slot is
    # bit-identical to sample_fast with the same template): EOS drawn at a
    # free position becomes the best non-EOS token, frozen positions take
    # the template token; slots with an all-False mask are untouched
    alt = (jnp.argmax(logits[:, 1:], axis=-1) + 1).astype(sampled.dtype)
    infill_on = jnp.any(slots.frozen, axis=1)
    sampled = jnp.where(infill_on & (sampled == 0), alt, sampled)
    frz = jnp.take_along_axis(slots.frozen, wpos[:, None], axis=1)[:, 0]
    tpl = jnp.take_along_axis(
        slots.template, wpos[:, None], axis=1
    )[:, 0].astype(sampled.dtype)
    sampled = jnp.where(frz, tpl, sampled)
    written = slots.seqs.at[jnp.arange(n_slots), wpos].set(sampled)
    seqs = jnp.where(slots.live[:, None], written, slots.seqs)
    nz = slots.nz + ((sampled == 0) & slots.live).astype(jnp.int32)
    cur = jnp.where(slots.live, slots.cur + 1, slots.cur)
    finished = slots.live & ((nz >= 2) | (cur >= slots.target - 1))
    new = SlotBatch(
        cache=cache,
        seqs=seqs,
        cur=cur,
        keys=keys,
        nz=nz,
        target=slots.target,
        temp=slots.temp,
        top_p=slots.top_p,
        top_k=slots.top_k,
        parity=slots.parity,
        live=slots.live & ~finished,
        template=slots.template,
        frozen=slots.frozen,
    )
    return new, sampled, slots.live, finished


@functools.partial(
    jax.jit, static_argnames=("model",), donate_argnums=(2,)
)
def _decode_step(model, params, slots):
    """Jitted decode step. ``params`` is ``ServeEngine.served_params``
    (an int8 engine's quantized pair is dequantized here, per channel,
    fused into the matmuls; never donated — read every step). ``slots``
    (arg 2) is DONATED — the hot-loop fix the PGL003 audit asked for:
    every decode step rebuilds the full pool (cache + per-slot state)
    and the caller rebinds ``self.slots``, so without donation the
    engine held two copies of the (max_slots, 2w) K/V pool across every
    step. Its trace records, by path, the cache-leaf writes it batched
    (``ServeEngine.row_write_paths``)."""
    params = dequantized(params, model.config.compute_dtype)
    with row_write_paths() as paths:
        out = _decode_step_impl(model, params, slots)
    _DECODE_ROW_WRITES[model] = dict(paths)
    return out


# per model, the paths the decode step's trace took for its batched cache
# writes (``layers._row_write_path``): written when the step is traced
_DECODE_ROW_WRITES: dict = {}


def seed_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` as host integers, computed on the
    CPU backend where there is one: on the accelerator the few integer
    operations would be programs of their own, queued behind the decode
    step in flight, and reading them back would hold the caller until
    that step is done. The journal writes these integers down; an
    admission hands them to ``_prefill_finish`` with its other host
    operands."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:  # a process held to the accelerator's platform
        cpu = None
    with jax.default_device(cpu):
        return np.asarray(jax.random.PRNGKey(seed))


def _match_placement(new, live):
    """Give a reloaded leaf the SAME placement key as the live one. The
    jit fastpath cache keys on (aval, sharding, committed): checkpoint
    restore hands back arrays committed to an explicit device while
    ``model.init`` params are uncommitted, and swapping one kind for the
    other silently recompiles the decode step on its next call — the
    exact thing a hot reload promises not to do."""
    if getattr(live, "committed", False):
        return jax.device_put(new, live.sharding)
    if getattr(new, "committed", False):
        # host round-trip is the only way to drop a committed placement;
        # runs on the reload background thread, never the serve loop
        return jnp.asarray(np.asarray(new))
    return new


@dataclasses.dataclass
class PendingPrefill:
    """Host-side state of an admission in progress — everything
    ``_prefill_finish`` will need, plus the batch-1 cache being fed.
    Lives OUTSIDE the pool until the final chunk: decode steps taken
    between chunks never observe a half-primed slot, and a crash
    mid-chunk loses nothing durable (the journal holds the accept; a
    replay re-runs the prefill from scratch or a prefix-cache hit).
    ``cache`` belongs to this admission ALONE from ``begin_prefill`` to
    the slot's activation: every program that touches it takes it
    donated and ``cache`` is rebound to what came back, so a reference
    kept to an earlier value is dead. Where a tree would be shared with
    the prefix cache one side gets a copy (``_copy_cache``, counted in
    ``copies``). Everything else rides on the host until the
    program that needs it: ``row`` goes to the device once, for the
    chunks, the scalars, the key and the template rows with the
    scatter's call. ``pos`` counts prime positions already fed (the feed
    region is ``0..start-2``; the last prime token is consumed by the
    first decode step)."""

    slot: int
    row: jnp.ndarray  # (max_len,) int32 padded token buffer
    host_row: np.ndarray  # the same buffer as the host built it
    start: int  # primed positions; feed region is row[0:start-1]
    length: int  # requested total length (the slot's target)
    key: Any  # per-request PRNG key (host integers when made from a seed)
    temperature: float
    top_p_val: float  # _TOP_P_OFF when off
    top_k_val: int  # 0 when off
    parity: bool
    trow: np.ndarray  # (max_len,) int32 infill template row
    frow: np.ndarray  # (max_len,) bool infill frozen row
    cache: Any  # batch-1 cache tree fed through ``pos`` positions
    pos: int = 0
    hit_depth: int = 0  # prefix-cache seed depth (0 = cold)
    blocks: int = 0  # feed blocks executed so far (``feed_block_count``)
    copies: int = 0  # batch-1 trees copied for the prefix cache's sake
    request_id: str = ""
    done: bool = False

    @property
    def feed_len(self) -> int:
        return max(self.start - 1, 0)

    @property
    def remaining(self) -> int:
        return self.feed_len - self.pos


class ServeEngine:
    """Fixed-pool continuous-batching engine bound to one (model, params,
    max_slots, max_len). Host-side it is a free-list, three jitted calls
    and a mirror of what the host itself put into each slot (the prime
    it admitted, the tokens it fetched); all decode state lives on the
    device in ``self.slots``, and the only read of it is the fetch of a
    decode step's three small outputs. A decode step takes nothing from
    the host, so ``launch_step`` may enqueue one before its predecessor
    was fetched: at most one step is ever launched and unfetched.
    ``params`` is the tree as handed in; the programs take
    ``served_params`` (see ``__init__``).

    Batch-1 caches: the engine owns the tree of every admission in
    flight (``PendingPrefill.cache``) and hands it to the chunk program
    and the scatter DONATED, so an admission copies and allocates
    nothing. A cold admission starts from ``new_cache()``: the tree the
    last scatter handed back with the model's initial values written
    over it, or, when there is none (the first admission was that of
    ``__init__``'s tree; a second admission in flight; a cancelled one),
    a run of the program that made the first. Only a prefix cache makes
    a tree shared, and there one side gets a copy: the insert at a chunk
    boundary leaves the tree to the cache and the admission goes on with
    a copy, a hit copies the stored tree for the admission
    (``_copy_cache``; ``prefill_cache_copies`` counts them). Without a
    prefix cache nothing is ever copied."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_len: Optional[int] = None,
                 quantize_int8: bool = False):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.max_len = int(max_len or model.config.seq_len)
        if not 2 <= self.max_len <= model.config.seq_len:
            raise ValueError(
                f"max_len must be in [2, seq_len={model.config.seq_len}], "
                f"got {self.max_len}"
            )
        self.max_slots = int(max_slots)
        # a family whose decode step takes all slots as one batch (a
        # model that says ``slot_batched``); what it cannot do is
        # refused by name
        self.slot_batched = bool(getattr(model, "slot_batched", False))
        if quantize_int8 and self.slot_batched:
            raise ValueError(
                f"{type(model).__name__} cannot be served in int8: "
                f"ops/quant.py quantizes the 2-D ``kernel`` leaves of "
                f"ProGen's Dense layers per output channel; a slot-batched "
                f"family names its weights itself (stacked expert weights "
                f"among them) and is served in the type its configuration "
                f"states"
            )
        self.model, self.params, cache1 = _decode_setup(
            model, params, batch=1, max_len=self.max_len
        )
        # the program that made ``cache1`` and the tree itself: what the
        # first cold admission is fed (``new_cache``)
        self._build_cache = cache_builder(self.model, self.params, 1)
        self._spare_cache = cache1
        # positions a prefill block holds: with ``feed_block_count`` the
        # host's account of the passes over the weights a prefill made
        self.prefill_width = feed_width(self.model.config)
        s, l = self.max_slots, self.max_len
        key0 = jax.random.PRNGKey(0)
        self.slots = SlotBatch(
            cache=jax.tree.map(
                lambda c: jnp.broadcast_to(c[None], (s,) + c.shape).copy(),
                cache1,
            ),
            seqs=jnp.zeros((s, l), jnp.int32),
            cur=jnp.zeros((s,), jnp.int32),
            keys=jnp.broadcast_to(
                key0[None], (s,) + key0.shape
            ).copy(),
            nz=jnp.zeros((s,), jnp.int32),
            target=jnp.full((s,), l, jnp.int32),
            temp=jnp.ones((s,), jnp.float32),
            top_p=jnp.full((s,), _TOP_P_OFF, jnp.float32),
            top_k=jnp.zeros((s,), jnp.int32),
            parity=jnp.ones((s,), bool),
            live=jnp.zeros((s,), bool),
            template=jnp.zeros((s, l), jnp.int32),
            frozen=jnp.zeros((s, l), bool),
        )
        self._free = list(range(s))
        # what the host knows of each slot without reading the device:
        # the token buffer (the prime it admitted, then every token it
        # fetched), the position of the last of them, the requested
        # length, whether the occupant is still decoding as far as a
        # fetched step has said, and a count of occupants — a step's
        # results belong to the occupants AS OF ITS LAUNCH
        self._targets = [l] * s
        self._rows = np.zeros((s, l), np.int32)
        self._cur = np.zeros((s,), np.int64)
        self._live = np.zeros((s,), bool)
        self._occupant = np.zeros((s,), np.int64)
        # the decode step launched and not yet fetched: its three device
        # outputs and ``_occupant`` as it stood at the launch
        self._in_flight = None
        # counts summed until the scheduler takes them (pop_counters)
        self._counters: dict = {}
        self._embed_model = None  # lazily built by embed()
        self._prefix_cache = None  # optional PrefixCache (set_prefix_cache)
        self.quantize_int8 = bool(quantize_int8)
        # ``params`` stays the tree as handed in (the checkpoint's type:
        # embed(), int8 quantization and a reload's dtype check read it);
        # the decode step and the prefills take the SERVED tree. A float
        # engine's holds every leaf the model would convert to the compute
        # type at each of its uses converted (Flax's rule;
        # tests/test_served_tree.py holds it to a trace of the programs)
        # and every other leaf as the raw array; it is built on demand,
        # see ``served_params``. An int8 engine's is the quantized pair: a
        # calibration, not a cast, so no leaf counts as cast and it is
        # built here and in ``prepare_params`` and nowhere else.
        self._cast = promoted_mask(
            self.params, self.model.config.compute_dtype
        )
        self._served = None
        self.quant_report = None
        if self.quantize_int8:
            self._cast = [False] * len(self._cast)
            self._served, self.quant_report = self._quantize(self.params)

    def _serve(self, params):
        return serve_tree(
            params, self._cast, self.model.config.compute_dtype
        )

    def _quantize(self, params):
        """(the pair the programs take, its calibration report)."""
        q, scales, leaves = quantize_tree(params)
        pair = QuantizedParams(q, scales)
        return pair, self._calibrate(leaves, params, pair)

    @property
    def served_params(self):
        """What the programs take. A float engine's tree lives while the
        engine holds a slot: ``release`` of the last one drops it (an idle
        engine holds the raw tree alone and leaves the rest of the device
        to whoever reads ``params`` — an embedding, a check against a
        reference, a reload's candidate) and the next admission builds it
        again, one conversion of the cast leaves. An int8 engine's pair is
        never dropped."""
        if self._served is None:
            self._served = self._serve(self.params)
        return self._served

    def _calibrate(self, leaves: list, params, pair) -> dict:
        """The logged accuracy contract of the int8 path: per-leaf weight
        max-abs-error from quantize_tree plus the worst logits
        max-abs-error of the dequantized weights vs the full-precision
        path over a fixed calibration prompt through a fresh cache (the
        exact op sequence decode runs). Takes the tree being calibrated
        explicitly so a hot reload can calibrate candidate weights while
        the live ones keep serving."""
        deq = dequantized(pair, self.model.config.compute_dtype)
        # not ``new_cache()``: a reload calibrates off the loop's thread
        cache_a = cache_b = self._build_cache()
        worst = 0.0
        for tok in (1, 7, 23, 4):  # fixed calibration prompt
            t = jnp.full((1, 1), tok, jnp.int32)
            la, mut_a = self.model.apply(
                {"params": params, "cache": cache_a}, t,
                mutable=["cache"],
            )
            cache_a = mut_a["cache"]
            lb, mut_b = self.model.apply(
                {"params": deq, "cache": cache_b}, t, mutable=["cache"]
            )
            cache_b = mut_b["cache"]
            worst = max(worst, float(jnp.max(jnp.abs(
                la.astype(jnp.float32) - lb.astype(jnp.float32)
            ))))
        report = {
            "bits": 8,
            "scheme": "per-channel symmetric, weights only",
            "quantized_leaves": len(leaves),
            "bytes_fp": sum(leaf["bytes_fp"] for leaf in leaves),
            "bytes_int8": sum(leaf["bytes_int8"] for leaf in leaves),
            "weight_max_abs_err": max(
                (leaf["max_abs_err"] for leaf in leaves), default=0.0
            ),
            "logits_max_abs_err": worst,
            "leaves": leaves,
        }
        logger.info(
            "int8 calibration: %s",
            {k: v for k, v in report.items() if k != "leaves"},
        )
        return report

    # ----- hot weight reload ---------------------------------------------

    def prepare_params(self, raw_params) -> PreparedParams:
        """Background half of a hot swap: bring a freshly restored param
        tree into this engine's decode layout and verify it is
        hot-swappable — identical treedef and per-leaf shape/dtype vs
        the live tree. Same shapes mean the compiled programs
        (prefill chunk, decode step) are reused verbatim, which is the whole
        zero-downtime contract; anything else raises ValueError and
        needs a restart, not a reload. Leaf placement is matched to the
        live tree (see ``_match_placement``) so the swap cannot change
        the jit cache key. Builds the candidate's served tree (the one
        conversion of a reload, here and not on the loop thread) and
        re-runs int8 quantization + calibration when
        the engine serves int8. Touches NO engine
        state (safe off-thread while decode_step runs); the loop thread
        applies the result with ``commit_params`` between steps."""
        from progen_tpu.models import unstack_params

        params = unstack_params(raw_params, self.model.config)
        ref = jax.tree_util.tree_flatten_with_path(self.params)
        new = jax.tree_util.tree_flatten_with_path(params)
        if ref[1] != new[1]:
            raise ValueError(
                "incompatible checkpoint: param tree structure differs "
                "from the live tree (different model architecture?) — "
                "hot reload needs a restart"
            )
        for (path, live), (_, cand) in zip(ref[0], new[0]):
            if live.shape != cand.shape or live.dtype != cand.dtype:
                raise ValueError(
                    f"incompatible checkpoint: param "
                    f"{jax.tree_util.keystr(path)} is "
                    f"{cand.shape}/{cand.dtype}, live tree has "
                    f"{live.shape}/{live.dtype} — hot reload needs a "
                    f"restart"
                )
        params = jax.tree.map(_match_placement, params, self.params)
        if self.quantize_int8:
            served, report = self._quantize(params)
            return PreparedParams(params, report, served)
        return PreparedParams(params, None, self._serve(params))

    def commit_params(self, prepared: PreparedParams) -> None:
        """Foreground half: rebind the served weights. The jitted
        programs take params as a per-call operand, so between two
        ``decode_step`` calls this is an atomic host-side swap — the
        next step LAUNCHED reads the new tree with zero recompiles
        (shape/dtype equality enforced by ``prepare_params``); a step
        already in flight ran on the old one. In-flight requests
        continue on their existing KV caches; only future matmuls see
        the new weights."""
        self.params = prepared.params
        self._served = prepared.served
        self.quant_report = prepared.quant_report
        if self._prefix_cache is not None:
            # snapshots are caches computed under the OLD weights —
            # serving one after the swap would silently answer with
            # stale-weight activations; drop them all (counters survive,
            # so the fleet console sees the invalidation as a bytes dip)
            self._prefix_cache.clear()

    # ----- prefix cache ---------------------------------------------------

    def set_prefix_cache(self, cache) -> None:
        """Attach a ``PrefixCache`` (serving/prefix_cache.py). Consulted
        by ``begin_prefill`` and fed at every chunk boundary by
        ``advance_prefill``; cleared on ``commit_params`` (snapshots are
        weight-dependent). A stored snapshot is the cache's own tree: no
        admission feeds it to a program (``_copy_cache``). The engine
        serves fine without one, and then copies no tree."""
        if self.slot_batched:
            raise ValueError(
                f"{type(self.model).__name__} cannot take a prefix cache: "
                f"a snapshot of a slot-batched family's state is its whole "
                f"batch-1 cache tree, max_len rows a layer whatever the "
                f"prefix's depth and, where part of it is a recurrence, "
                f"good at the depth it was stored at alone, while "
                f"prefix_cache.py budgets whole cache trees and resumes "
                f"from any shorter prefix"
            )
        self._prefix_cache = cache

    @property
    def prefix_cache(self):
        return self._prefix_cache

    # ----- slot lifecycle -------------------------------------------------

    @property
    def num_active(self) -> int:
        return self.max_slots - len(self._free)

    @property
    def any_live(self) -> bool:
        return len(self._free) < self.max_slots

    def acquire(self) -> Optional[int]:
        """Claim the lowest free slot (deterministic assignment), or None
        when the pool is saturated."""
        if not self._free:
            return None
        self._free.sort()
        return self._free.pop(0)

    def release(self, slot: int) -> None:
        """Return a finished (or cancelled) slot to the free list. Device
        state is NOT scrubbed — the next prefill fully rewrites it. No
        read of the device: whether the occupant still decodes is what
        the fetched steps have told the host. A cancelled slot that does
        is silenced by an update enqueued behind whatever is in flight,
        so it stops burning steps; what a step already launched draws
        for it belongs to nobody and is masked out of that step's
        fetch."""
        if slot in self._free:
            return
        if self._live[slot]:
            self.slots = self.slots._replace(
                live=self.slots.live.at[slot].set(False)
            )
            self._live[slot] = False
        self._occupant[slot] += 1
        self._free.append(slot)
        if not self.any_live and not self.quantize_int8:
            self._served = None  # idle: see ``served_params``

    # ----- request admission ---------------------------------------------

    def validate(self, prime, length, *, add_bos: bool = False,
                 temperature: float = 1.0, top_p=None, top_k=25,
                 template=None, frozen=None) -> None:
        """Raise ValueError for anything the pool cannot serve — the same
        checks the standalone decoders apply, plus the pool's max_len
        bound and the dynamic sampler's top_k range. Cheap (no device
        work beyond the prime copy); the scheduler rejects on this at
        submit time so invalid requests never occupy queue space."""
        if length > self.max_len:
            raise ValueError(
                f"length {length} exceeds engine max_len {self.max_len}"
            )
        _validate_knobs(temperature, top_p)
        if top_k is not None and not (
            1 <= int(top_k) <= self.model.config.num_tokens
        ):
            raise ValueError(
                f"top_k must be None or in [1, {self.model.config.num_tokens}]"
                f", got {top_k}"
            )
        vocab = self.model.config.num_tokens
        _validate_infill(template, frozen, length, vocab)
        ids = np.asarray(prime).reshape(-1)
        if ids.size and not (0 <= int(ids.min()) and int(ids.max()) < vocab):
            raise ValueError(f"prime token ids must be in [0, {vocab})")
        _prepare_seq(self.model, prime, length, add_bos)

    def _prepare_admission(self, prime, length, *, top_k, add_bos,
                           temperature, top_p, key, seed, template,
                           frozen):
        """Validation + host-side row construction of an admission.
        Returns (row, start, key, parity, trow, frow)."""
        with _stage("serve/prepare"):
            self.validate(prime, length, add_bos=add_bos,
                          temperature=temperature, top_p=top_p, top_k=top_k,
                          template=template, frozen=frozen)
            seq, start = _prepare_seq(self.model, prime, length, add_bos)
            row = np.zeros((self.max_len,), np.int32)
            row[: seq.shape[0]] = seq
            trow = np.zeros((self.max_len,), np.int32)
            frow = np.zeros((self.max_len,), bool)
            if template is not None:
                trow[:length] = np.asarray(template, np.int32).reshape(-1)
                frow[:length] = np.asarray(frozen, bool).reshape(-1)
            if key is None:
                key = seed_key(seed)
            parity = temperature == 1.0 and top_p is None
            return row, int(start), key, parity, trow, frow

    def prefill(self, slot: int, prime, length: int, *,
                top_k=25, add_bos: bool = False, temperature: float = 1.0,
                top_p=None, key=None, seed: int = 0,
                request_id: Optional[str] = None,
                template=None, frozen=None) -> int:
        """Admit a request into ``slot`` in one call (direct callers; the
        scheduler drives ``begin_prefill`` / ``advance_prefill`` itself):
        the whole prime as one chunk. Returns the number of primed
        positions (``start``). The slot's stream is bit-identical to
        ``sample_fast(key, model, params, prime, length, ...)``.
        ``template``/``frozen`` ((length,) arrays) enable fixed-position
        infilling for this slot, matching ``sample_fast``'s constraint.
        ``request_id`` is telemetry-only: the prefill span carries it so
        the trace ties device work back to the request's async track."""
        with _span("serve/prefill", slot=int(slot),
                   request_id="" if request_id is None else str(request_id)):
            pending = self.begin_prefill(
                slot, prime, length, top_k=top_k, add_bos=add_bos,
                temperature=temperature, top_p=top_p, key=key, seed=seed,
                request_id=request_id, template=template, frozen=frozen,
            )
            self.advance_prefill(pending)
            return pending.start

    def prefill_blocks(self, lo: int, hi: int) -> int:
        """Blocks a prefill of positions ``[lo, hi)`` executes (host
        arithmetic): ``prefill_tokens / (prefill_blocks * prefill_width)``
        is the share of computed prefill rows that were real."""
        return feed_block_count(self.prefill_width, lo, hi)

    # ----- admission in chunks --------------------------------------------

    def begin_prefill(self, slot: int, prime, length: int, *,
                      top_k=25, add_bos: bool = False,
                      temperature: float = 1.0, top_p=None, key=None,
                      seed: int = 0, request_id: Optional[str] = None,
                      template=None, frozen=None) -> PendingPrefill:
        """Start an admission into ``slot``: validate + build the
        operands but run NO program yet (the prime's row goes to the
        device; without the recycled tree, see ``new_cache``, the
        cache's builder runs) — the caller (the scheduler)
        advances the returned ``PendingPrefill`` with
        ``advance_prefill`` between decode steps. When a prefix
        cache is attached, the longest cached prefix of the feed region
        seeds the pending state at its depth — with a copy of the stored
        snapshot, which stays the cache's — so a repeated scaffold
        skips straight to the tail. The eventual token stream does not
        depend on how ``advance_prefill`` splits the prime."""
        row, start, key, parity, trow, frow = self._prepare_admission(
            prime, length, top_k=top_k, add_bos=add_bos,
            temperature=temperature, top_p=top_p, key=key, seed=seed,
            template=template, frozen=frozen,
        )
        pending = PendingPrefill(
            slot=int(slot),
            row=jax.device_put(row),
            host_row=row,
            start=start,
            length=int(length),
            key=key,
            temperature=float(temperature),
            top_p_val=float(_TOP_P_OFF if top_p is None else top_p),
            top_k_val=int(0 if top_k is None else top_k),
            parity=bool(parity),
            trow=trow,
            frow=frow,
            cache=None,
            request_id="" if request_id is None else str(request_id),
        )
        if self._prefix_cache is not None:
            depth, snap = self._prefix_cache.lookup(row, pending.feed_len)
            if snap is not None:
                pending.cache = _copy_cache(snap)
                pending.copies = 1
                pending.pos = pending.hit_depth = int(depth)
        if pending.cache is None:
            pending.cache = self.new_cache()
        return pending

    def new_cache(self):
        """A batch-1 cache tree holding the model's initial values, the
        caller's own (it may donate it): the tree the last scatter handed
        back, else a new one from the program that made the first."""
        cache, self._spare_cache = self._spare_cache, None
        return self._build_cache() if cache is None else cache

    def advance_prefill(self, pending: PendingPrefill,
                        budget: Optional[int] = None) -> bool:
        """Feed up to ``budget`` more prime positions (all remaining
        when None) through the pending batch-1 cache; when the feed
        region is exhausted, scatter + activate the slot in the same
        call (the slot scatter happens ONLY on this final chunk).
        Chunk boundaries are snapshotted into the prefix cache: what it
        stores becomes its own and the admission goes on with a copy.
        Returns True once the slot is live. ``lo``/``hi`` ride as traced
        operands, so every chunk size reuses one compiled program — as
        HOST scalars, like the scatter's: a ``jnp`` scalar would be a
        program of its own each, queued behind the step in flight (0.6
        ms of the caller's time against 0.24 on a v5e, PERF.md, PR 37)."""
        if pending.done:
            return True
        feed_len = pending.feed_len
        hi = feed_len if budget is None else min(
            feed_len, pending.pos + max(int(budget), 0)
        )
        with _span("serve/prefill_chunk", slot=int(pending.slot),
                   request_id=pending.request_id,
                   lo=int(pending.pos), hi=int(hi)):
            if hi > pending.pos:
                with _stage("serve/prefill_dispatch"):
                    pending.cache = _prefill_chunk(
                        self.model, self.served_params, pending.cache,
                        pending.row, np.int32(pending.pos), np.int32(hi),
                    )
                pending.blocks += self.prefill_blocks(pending.pos, int(hi))
                pending.pos = int(hi)
                if self._prefix_cache is not None:
                    with _stage("serve/prefix_insert"):
                        if self._prefix_cache.insert(
                            pending.host_row, pending.pos, pending.cache,
                        ):
                            pending.cache = _copy_cache(pending.cache)
                            pending.copies += 1
            if pending.pos >= feed_len:
                with _stage("serve/prefill_finish"):
                    tail = (
                        np.int32(pending.slot), pending.row,
                        np.int32(pending.start), np.int32(pending.length),
                        pending.key,
                        np.float32(pending.temperature),
                        np.float32(pending.top_p_val),
                        np.int32(pending.top_k_val),
                        np.bool_(pending.parity),
                        pending.trow, pending.frow,
                    )
                    self.slots, self._spare_cache = _prefill_finish(
                        self.slots, pending.cache, *tail,
                        new_cache=self._build_cache,
                    )
                    pending.cache = None
                self._count("prefill_cache_copies", pending.copies)
                slot = pending.slot
                self._targets[slot] = int(pending.length)
                self._rows[slot] = pending.host_row
                self._cur[slot] = pending.start - 1
                self._live[slot] = True
                self._occupant[slot] += 1
                pending.done = True
        return pending.done

    # ----- the hot loop ---------------------------------------------------

    @property
    def step_in_flight(self) -> bool:
        """A decode step is launched and not yet fetched."""
        return self._in_flight is not None

    def launch_step(self) -> None:
        """Enqueue one decode step and return without waiting for it:
        the step takes nothing from the host (liveness, the stop and
        infill rules and the sampler's keys live in ``slots``), so it is
        fully determined the moment its predecessor is enqueued. At most
        one step is in flight: ``decode_step`` fetches it."""
        if self._in_flight is not None:
            raise RuntimeError("a decode step is already in flight")
        with _stage("serve/decode_dispatch"):
            self.slots, *outputs = _decode_step(
                self.model, self.served_params, self.slots
            )
        self._in_flight = (outputs, self._occupant.copy())

    def drop_step(self) -> None:
        """Forget the step in flight, if any, without waiting for it —
        for a caller that knows it advanced no slot (every occupant had
        finished or been released before its launch was due)."""
        if self._in_flight is not None and self._live.any():
            raise RuntimeError("the step in flight may hold live slots")
        self._in_flight = None

    def decode_step(self):
        """One token for every live slot. Returns host arrays
        (sampled, was_live, finished), each (max_slots,) — ``sampled[i]``
        is meaningful only where ``was_live[i]``.

        With nothing in flight: launch a step, fetch it, return it. With
        a step in flight (``launch_step``): launch its SUCCESSOR first —
        unless no occupant is left that a fetched step has not seen
        finish —, then fetch and return the one that was in flight: the
        device holds a whole step of queued work while the host reads,
        and the fetch is the one wait on the device. The results are
        those of the occupants as of the step's launch: where a slot was
        released since (a cancellation; a new request may hold it by
        now), ``was_live`` and ``finished`` read False."""
        fetch, self._in_flight = self._in_flight, None
        if fetch is None:
            self.launch_step()
            fetch, self._in_flight = self._in_flight, None
        elif self._live.any():
            self.launch_step()
        outputs, occupant = fetch
        with _stage("serve/decode_fetch"):
            # one wait for the three of them, not one after the other
            sampled, was_live, finished = jax.device_get(outputs)
            if self.slot_batched:
                # the family's counts ride behind the tokens; it names
                # and folds them itself
                folded = self.model.fold_counts(
                    sampled[self.max_slots:], int(was_live.sum())
                )
                for name, by in folded.items():
                    self._count(name, by)
                sampled = sampled[: self.max_slots]
            same = occupant == self._occupant
            was_live, finished = was_live & same, finished & same
            wrote = np.flatnonzero(was_live)
            if not self.slot_batched:
                self._count_ring_rows(self._cur[wrote])
            elif hasattr(self.model, "attend_rows"):
                for name, by in self.model.attend_rows(self._cur[wrote]).items():
                    self._count(name, by)
            self._cur[wrote] += 1
            self._rows[wrote, self._cur[wrote]] = sampled[wrote]
            self._live[finished] = False
            return sampled, was_live, finished

    def _count_ring_rows(self, pos) -> None:
        """What a decode step's attention read of the K/V rings of the
        slots it advanced (queries at ``pos``), a layer: the ring blocks
        the kernel lists for them, or whole rings where the step runs the
        plain form. Host arithmetic on positions the host holds: the
        quotient of the two counters is the share of the rings' rows a
        step reads."""
        c = self.model.config
        ring = 2 * c.window_size
        block = kernel_block(
            c.window_size, c.heads, c.dim_head, c.compute_dtype
        )
        held = ring * len(pos)
        read = held if block is None else int(
            listed_rows(pos, c.window_size, ring, block).sum()
        )
        self._count("ring_rows_read", read)
        self._count("ring_rows_held", held)

    def _count(self, name: str, by: int) -> None:
        self._counters[name] = self._counters.get(name, 0) + by

    def pop_counters(self) -> dict:
        """Counter increments gathered since the last call; the scheduler
        adds them to its ``ServingMetrics``: a family's own counts, the
        ring rows of ProGen's decode steps, the attention rows of a family
        that counts them (``model.attend_rows``), and ``prefill_cache_copies``
        (batch-1 trees copied because a prefix cache shares them, counted
        when their admission activates: 0 without one)."""
        out, self._counters = self._counters, {}
        return out

    def state_bytes(self) -> dict:
        """Gauges of the engine's state, by kind. The served tree shares
        every leaf that is not cast with the raw one, so while it lives
        the device holds ``raw_weight_bytes`` plus the cast leaves' served
        bytes (counted from shapes: an idle engine has dropped it)."""
        itemsize = jnp.dtype(self.model.config.compute_dtype).itemsize
        leaves = jax.tree.leaves(self.params)
        out = {
            "raw_weight_bytes": sum(leaf.nbytes for leaf in leaves),
            "served_weight_bytes": sum(
                leaf.size * itemsize if cast else leaf.nbytes
                for leaf, cast in zip(leaves, self._cast)
            ),
            "served_leaves_cast": sum(self._cast),
        }
        # the pool's state by kind, where the family names kinds: a cache
        # leaf's name -> the gauge that counts its bytes ("*": any other)
        kinds = getattr(self.model, "cache_kinds", None) or {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.slots.cache):
            gauge = kinds.get(path[-1].key, kinds.get("*"))
            if gauge:
                out[gauge] = out.get(gauge, 0) + leaf.nbytes
        out.update(self.row_write_paths())
        return out

    def row_write_paths(self) -> dict:
        """Gauges of how the decode step writes its cache leaves, each
        leaf's new rows for all slots at once (``layers._update_at``):
        ``cache_write_leaves_<path>``, the leaf writes that took the
        row-write kernel, the one select over a small leaf, or one update
        a slot (``layers._row_write_path``). Recorded when the step is
        traced: all 0 until its first launch."""
        paths = _DECODE_ROW_WRITES.get(self.model, {})
        return {
            f"cache_write_leaves_{path}": paths.get(path, 0)
            for path in ("kernel", "select", "loop")
        }

    def collect(self, slot: int) -> np.ndarray:
        """The slot's (target,) token buffer with the standalone
        decoders' truncation applied (everything after the second zero
        -> 0), so it compares token-for-token with ``sample_fast``
        output. Built from what the host holds — the prime it admitted
        and every token it fetched, which is what ``slots.seqs`` holds on
        the device once the slot's last step is fetched — not from a
        read of the pool."""
        row = self._rows[slot, : self._targets[slot]].copy()
        row[np.cumsum(row == 0) > 1] = 0
        return row

    # ----- embeddings extraction ------------------------------------------

    def check_embeddable(self) -> None:
        if self.slot_batched:
            raise ValueError(
                f"{type(self.model).__name__} serves no embeddings: "
                f"workloads/embeddings.py pools ProGen's final norm"
            )

    def embed(self, prime, *, add_bos: bool = False) -> np.ndarray:
        """Final-norm mean-pooled representation of ``prime`` — the
        embeddings-extraction request type (workloads/embeddings.py).
        Runs a lazily built NON-decode twin of the served model (one full
        forward, no KV cache) against the engine's full-precision params
        — also under int8 serving, where weight-only quantization exists
        to protect exactly this kind of read-out quality. Lengths are
        power-of-two bucketed so a ragged request stream reuses a few
        compiled programs; gMLP models pad to the full seq_len (their
        SGU matrix admits nothing narrower). Returns (dim,) float32."""
        from progen_tpu.workloads.embeddings import bucket_length, embed_step

        self.check_embeddable()
        prime = np.asarray(prime, np.int32).reshape(-1)
        if add_bos:
            prime = np.concatenate([np.zeros((1,), np.int32), prime])
        if prime.shape[0] == 0:
            raise ValueError("empty prime requires add_bos=True")
        cfg = self.model.config
        if self._embed_model is None:
            import dataclasses

            self._embed_model = type(self.model)(
                dataclasses.replace(cfg, decode=False, scan_layers=False),
                mesh=getattr(self.model, "mesh", None),
            )
        n = bucket_length(
            int(prime.shape[0]), cfg.seq_len,
            minimum=max(8, cfg.window_size),
            fixed=cfg.global_mlp_depth > 0,
        )
        row = np.zeros((1, n), np.int32)
        row[0, : prime.shape[0]] = prime
        with _span("serve/embed", n_tokens=int(prime.shape[0])):
            out = embed_step(
                self._embed_model, self.params, jnp.asarray(row)
            )
        return np.asarray(out[0], np.float32)

    # ----- introspection --------------------------------------------------

    @staticmethod
    def decode_compile_count() -> int:
        """Number of compiled variants of the decode step across ALL
        engines in the process — the jit-cache-miss counter the
        compile-once acceptance test asserts on."""
        return _decode_step._cache_size()

    @staticmethod
    def prefill_compile_count() -> int:
        """Compiled variants of the admission's programs — the chunk, the
        scatter, the copy a prefix cache asks for — across ALL engines
        in the process. Flat-after-warmup is the acceptance bar (traced
        bounds are what keep the chunk program at one)."""
        return (_prefill_chunk._cache_size() + _prefill_finish._cache_size()
                + _copy_cache._cache_size())

"""Generation cells of the window_moe family (sliding-window and full
attention side by side, routed experts held as one chip's share): the
closed loop of ``drivers/gen_latent_moe.py`` over token ids of the model's
own vocabulary slice, through ``Scheduler`` in front of ``ServeEngine``
built as ``cli/serve.py:_build`` builds them (``models.build_model`` on
the cell's configuration), on seeded bfloat16 weights. The client's own
clock around ``Scheduler.step()`` times every token; the family's counters
(routed and held assignments, held experts touched) come out of
``ServingMetrics`` like the others.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import stats
from benchmark import traffic as traffic_mod
from benchmark.drivers.gen import compared, make_request, real_row_share
from benchmark.reference import window_moe_ref
from progen_tpu.models import build_model  # a program without it fails here
from progen_tpu.sampling import gumbel_step_dynamic

# The served model holds bfloat16 weights and computes in bfloat16 (f32
# accumulation inside a product; f32 router, softmax and logits); the
# reference reads the same weights and computes in float32 throughout, at
# the expert choices the system made (window_moe_ref.forward says why).
# Logits are judged against the standard deviation of the reference's over
# the 32 decoded positions x the vocabulary slice; two attention outputs,
# the first sliding layer's and the first full layer's at the decoded
# positions, by the norm of their error over the reference's norm: with
# random weights attention is spread over many rows, so a window ignored
# or a rotation misplaced moves the logits by little and these by much.
# Every limit stands between a sound reading and a faulty one (PERF.md
# section 6; the faults were planted under this same function, in the
# re-feed that the check reads, on the cell's first weights: unit
# post-attention scales, a table not centred). Over 29 runs on a TPU v5e at
# the cell's own size (prompt 7,168, 32 decoded positions) the sound system
# read at most RMS 0.99% and largest 5.5% of the logits' deviation,
# attention outputs 0.55% (sliding) and 1.27% (full), slack 0.0029,
# exchanged 7.0%, and redrew 32 of 32; PERF.md holds every seed's.
#   logits - the reference with every matrix rounded to the 8-bit float (5
#     exponent bits, 2 of mantissa) below the bfloat16 the configuration
#     states: RMS 82%, largest 412%; a share off by one expert 29% / 212%;
#     a window ignored in the sliding layers 17% / 78%.
#   attention outputs - a window ignored 27% (sliding); RoPE on the full
#     layers 16% (full; the sliding one unmoved at 0.48%); the 8-bit
#     reference 29% / 84%.
#   decisions, which the logits' limits cannot see (the reference is
#     handed them) - how far an expert the system used may stand below the
#     reference's own 4th best score (slack), and the share of (position,
#     expert layer) choices that are not the reference's own (exchanged):
#     ties among 256 sigmoid scores that the bfloat16 rounding of the
#     router's input turns; every fault above moves the router's input and
#     reads 0.018-0.44 and 24-98%.
#   the timed program - of the 32 tokens engine.decode_step drew with all
#     32 slots live, gumbel_step_dynamic redraws all from the logits this
#     check reads alone; a pool-wide sampler ignoring top_k redraws none.
RMS_TOLERANCE = 0.05
MAX_TOLERANCE = 0.30
ATTN_TOLERANCE = 0.05
SLACK_TOLERANCE = 0.014
EXCHANGED_TOLERANCE = 0.2
REDRAWN_AT_LEAST = 0.75
LIMITS = (("rms_err_over_std", RMS_TOLERANCE),
          ("max_err_over_std", MAX_TOLERANCE),
          ("window_attn_err_over_norm", ATTN_TOLERANCE),
          ("full_attn_err_over_norm", ATTN_TOLERANCE),
          ("routing_slack", SLACK_TOLERANCE),
          ("exchanged_share", EXCHANGED_TOLERANCE))


# Random weights make attention average its whole context; the
# post-attention norm scales that average up into a direction all of a
# sequence's positions share, and the router follows it. At unit scales a
# sequence's share of the assignments on this chip's experts spread by
# 4-6% (absolute) from one sequence to the next, and the runs with it
# (PERF.md section 6); at 1/8 by 1%, the spread that 1,024 independent
# assignments have (the CPU, at the published widths). The published
# sandwich norm is "depth-scaled": its scales start below one, by a rule
# the config does not give.
POST_ATTN_NORM_SCALE = 0.125
BIAS_STD = 0.01  # decides the close choices, as a trained bias does


def seeded_params(model, seed: int, device):
    """The model's parameters from the seed, made on the device in the
    type the configuration states, with four departures from the family's
    initialisation, each so that what a run computes does not move with
    the seed where a trained model's would not (PERF.md section 6):

    * the embedding table centred: its mean row is what every position of
      every sequence would share (router inputs of different sequences
      pointed alike by a cosine of 0.33 at 1.5-2k positions, 0.02
      centred);
    * the post-attention norms' scales at ``POST_ATTN_NORM_SCALE``;
    * each router's columns orthogonal, of the norm its normal draw has on
      average (0.02 x sqrt(hidden)), so that no expert is chosen more
      often for its draw;
    * ``expert_bias`` drawn at ``BIAS_STD`` (the family starts it at zero,
      which would leave the bias' part in choosing experts untested) less
      its mean over each share of ``num_experts`` experts, so that no
      chip's share is favoured on the whole: a trained router's bias
      balances its loads. With a bias that was not centred, the share of
      assignments this chip held spread by 2.6% (relative) over six
      seeds."""
    c = model.config
    params = traffic_mod.seeded_params(model, 8, seed, device)
    key = traffic_mod.fold_key(seed + 1)

    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, sum(name.encode()))
        if "embedding" in name:
            table = leaf.astype(jnp.float32)
            return (table - table.mean(0)).astype(leaf.dtype)
        if "post_attn_norm" in name:
            return jnp.full_like(leaf, POST_ATTN_NORM_SCALE)
        if "w_router" in name:
            norm = 0.02 * leaf.shape[0] ** 0.5
            return jax.nn.initializers.orthogonal(norm)(
                k, leaf.shape, jnp.float32).astype(leaf.dtype)
        if "expert_bias" in name:
            bias = BIAS_STD * jax.random.normal(k, leaf.shape, leaf.dtype)
            shares = bias.reshape(-1, c.num_experts)
            return (shares - shares.mean(1, keepdims=True)).reshape(leaf.shape)
        return leaf

    with jax.default_device(device):
        return jax.tree_util.tree_map_with_path(draw, params)


def build(run):
    """(scheduler, engine, journal) as cli/serve.py:_build makes them."""
    from progen_tpu.serving import Scheduler, ServeEngine
    from progen_tpu.serving.journal import RequestJournal

    t = run.traffic
    model = build_model(run.config)
    params = seeded_params(model, run.seed, run.devices[0])
    with jax.default_device(run.devices[0]):
        engine = ServeEngine(model, params, max_slots=t["max_slots"],
                             max_len=t["max_len"])
    journal = None
    if t["journal"]:
        journal = RequestJournal(run.tmp / "journal" / "journal.jsonl")
    sched = Scheduler(engine, max_queue=t["max_queue"], journal=journal,
                      prefill_chunk=t["prefill_chunk"])
    return sched, engine, journal


def gen_requests(traffic: dict, vocab: int, seed: int):
    """Endless (prompt ids, output length): every pair of the grid once a
    cycle (``traffic.paired_cycles``) in an order that is the cell's own
    (``size_order_seed``), ids uniform in 1..vocab-1 from the seed: a
    request lasts a good part of the window, so a window holds a few
    cycles and a seed that permuted them would make runs do different
    work)."""
    pairs = traffic_mod.paired_cycles(
        traffic["prompt_lengths"], traffic["output_lengths"],
        traffic_mod.rng_for(traffic["size_order_seed"], "order"),
    )
    rng = traffic_mod.rng_for(seed, "gen")
    for p_len, out_len in pairs:
        yield rng.integers(1, vocab, size=p_len, dtype=np.int32), out_len


def layer_of(cfg: dict, kind: str) -> int:
    return cfg["layer_types"].index(kind)


def check_against_reference(run, engine, reference_kwargs=None) -> dict:
    """Fill the pool through the engine's own chunked admission — the
    checked request (``check_prompt`` ids, past the window so that every
    sliding ring has wrapped, and ``check_positions`` answers) in one
    slot, grid prompts at their own depths in all the others — and let the
    TIMED program, ``engine.decode_step``, draw the answer. Then feed that
    answer again through the POOL's cache (handed over donated and given
    back: a second pool would not fit beside the first) with the served
    model call, all slots one batch and this one live, to read what the
    timed program does not return: the logits, the experts chosen and two
    attention outputs at every decoded position. Comparisons: the logits
    and the attention outputs against the reference's forward over the
    same tokens; the choices against the reference's own scores (it is
    handed the system's experts at the decoded positions and says how many
    it would not have chosen, and by how much); and the tokens the timed
    program drew, all slots live, against ``gumbel_step_dynamic`` on the
    logits read alone. ``reference_kwargs`` is for a control (a lower
    precision)."""
    t, cfg = run.traffic, run.config
    rng = traffic_mod.rng_for(run.seed, "check")
    n_prompt, n_check = t["check_prompt"], t["check_positions"]
    lengths = [n_prompt] + [
        t["prompt_lengths"][i % len(t["prompt_lengths"])]
        for i in range(1, engine.max_slots)
    ]
    held = []
    for i, p_len in enumerate(lengths):
        prompt = rng.integers(1, cfg["vocab_size"], size=p_len, dtype=np.int32)
        # the others outlive the checked request, so every step has them
        req = make_request(f"check{i}", prompt, n_check + (8 if i else 0), t)
        held.append(engine.acquire())
        pending = engine.begin_prefill(
            held[-1], req.prime, req.length, add_bos=True, top_k=req.top_k,
            temperature=req.temperature, seed=req.seed,
            template=req.template, frozen=req.frozen,
        )
        while not engine.advance_prefill(pending, t["prefill_chunk"]):
            pass
        if i == 0:
            row = [0, *prompt]  # BOS first
    slot, pool = held[0], engine.slots
    knobs = [np.asarray(a[slot]) for a in (
        pool.keys, pool.top_k, pool.parity, pool.temp, pool.top_p)]
    all_live = True
    for _ in range(n_check):
        sampled, was_live, _ = engine.decode_step()
        all_live &= bool(was_live.all())
        row.append(int(sampled[slot]))
    for s in held:
        engine.release(s)
    row = np.asarray(row, np.int32)

    n_slots = engine.max_slots
    live = (jnp.arange(n_slots) == slot)[:, None]
    first = cfg["num_dense_layers"]
    moe = [f"ffn{i}" for i in range(first, cfg["num_hidden_layers"])]
    attn = [layer_of(cfg, "sliding_attention"), layer_of(cfg, "full_attention")]

    def decode(params, cache, toks, key, top_k, parity, temp, top_p):
        def one(carry, tok):  # WindowMoE.decode_slots, keeping what it met
            cache, pos, key = carry
            (logits, _), mut = engine.model.apply(
                {"params": params,
                 "cache": jax.tree.map(lambda c: c[:, 0], cache)},
                jnp.full((n_slots, 1), tok), jnp.full((n_slots, 1), pos),
                live, mutable=["cache", "intermediates"],
            )
            inter = mut["intermediates"]
            chosen = jnp.stack([inter[name]["experts"][0][slot] for name in moe])
            outs = jnp.stack([inter[f"attn{i}"]["out"][0][slot, 0] for i in attn])
            cache = jax.tree.map(lambda c: c[:, None], mut["cache"])
            logit = logits[slot, 0]
            key, drawn = gumbel_step_dynamic(key, logit, top_k, parity,
                                             temp, top_p)
            # the template's rule: a drawn EOS becomes the best other id
            drawn = jnp.where(drawn == 0, jnp.argmax(logit[1:]) + 1, drawn)
            return (cache, pos + 1, key), (logit, chosen, outs, drawn)

        (cache, _, _), out = jax.lax.scan(
            one, (cache, jnp.int32(n_prompt), key), toks)
        return cache, out

    # the prefill fed row[0:n_prompt]; decoding feeds row[n_prompt:-1]:
    # the rows the timed steps wrote at those positions are written again
    cache, (got, chosen, outs, drawn) = jax.jit(decode, donate_argnums=(1,))(
        engine.params, engine.slots.cache, jnp.asarray(row[n_prompt:-1]), *knobs)
    engine.slots = engine.slots._replace(cache=cache)
    own = np.full((n_prompt, cfg["num_experts_per_tok"]), -1, np.int32)
    want, aux = window_moe_ref.forward(
        engine.params, jnp.asarray(row[:-1]), cfg, return_aux=True,
        logits_from=n_prompt, attention_of=tuple(attn),
        experts=[np.concatenate([own, np.asarray(chosen[:, i])])
                 for i in range(len(moe))],
        **(reference_kwargs or {}),
    )
    check = stats.relative_errors(got, want)
    for name, i, k in (("window", attn[0], 0), ("full", attn[1], 1)):
        mine = np.asarray(outs[:, k], np.float64)
        theirs = np.asarray(aux["attention"][i], np.float64)
        check[f"{name}_attn_err_over_norm"] = float(
            np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))
    slack = np.stack([np.asarray(s)[n_prompt:] for s in aux["slack"]])
    check["routing_slack"] = float(slack.max())
    check["exchanged_share"] = float((slack > 0).mean())
    check["served_tokens_redrawn"] = int(
        (np.asarray(drawn) == row[n_prompt + 1:]).sum()
    )
    return {"positions": int(got.shape[0]), **check,
            "tolerances": [RMS_TOLERANCE, MAX_TOLERANCE, ATTN_TOLERANCE,
                           SLACK_TOLERANCE, EXCHANGED_TOLERANCE,
                           REDRAWN_AT_LEAST],
            "ok": all_live
            and all(check[name] <= limit for name, limit in LIMITS)
            and check["served_tokens_redrawn"]
            >= REDRAWN_AT_LEAST * got.shape[0]}


COUNTERS = (
    "decode_steps", "decode_steps_ahead", "decode_tokens", "prefill_tokens",
    "prefill_blocks", "prefill_time_s", "decode_time_s", "moe_expert_layer_steps",
    "moe_assignments", "moe_held_assignments", "moe_experts_touched",
    "moe_max_load_rows", "moe_feed_expert_layer_blocks",
    "moe_feed_experts_touched", "moe_feed_max_load_rows",
    "moe_feed_held_assignments", "moe_feed_assignments",
)
GAUGES = ("window_cache_bytes", "kv_cache_bytes", "served_weight_bytes")


def window_rows(n: int, window: int) -> int:
    """sum over m = 1..n of min(m, window): the rows positions 0..n-1 see
    in a sliding layer."""
    if n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def run(run) -> dict:
    t = run.traffic
    sched, engine, journal = build(run)
    vocab = engine.model.config.num_tokens
    window = engine.model.config.sliding_window
    check = check_against_reference(run, engine)
    # the check's programs (the reference's, the re-feed's) leave Python
    # objects a server would not hold: moved out of the collector's way,
    # and every full collection in the window is timed (``notes``)
    gc.collect()
    gc.freeze()
    collections = []  # [start, seconds] of each full collection

    def timed(phase, info):
        if info["generation"] == 2 and phase == "start":
            collections.append([time.perf_counter(), None])
        elif info["generation"] == 2 and collections:
            collections[-1][1] = time.perf_counter() - collections[-1][0]

    gc.callbacks.append(timed)
    if run.tracing:
        run.wrap(engine, "decode_step", "engine.decode_step")
        run.wrap(engine, "advance_prefill", "engine.prefill")
        if journal is not None:
            run.wrap(journal, "emit", "journal")

    # every (prompt, total length) shape the window will submit goes once
    # through validation now, as in the other generation cells
    for p_len in t["prompt_lengths"]:
        for out_len in t["output_lengths"]:
            req = make_request("warm", np.ones((p_len,), np.int32), out_len, t)
            engine.validate(req.prime, req.length, add_bos=True,
                            top_k=req.top_k, temperature=req.temperature,
                            template=req.template, frozen=req.frozen)

    requests = gen_requests(t, vocab, run.seed)
    n_clients = t["clients"]
    owner, expect, submit_t, fed = {}, {}, {}, {}
    token_t, token_at, ttft = {}, {}, {}
    n_submitted = n_rejected = n_wrong = n_done = 0
    first_seen, replaced = set(), 0

    def submit(client: int, residual: float = 1.0):
        nonlocal n_submitted, n_rejected
        prompt, out_len = next(requests)
        out_len = max(1, round(out_len * residual))
        rid = f"c{client}-{n_submitted}"
        with run.span("sched.submit"):
            ok, _ = sched.submit(make_request(rid, prompt, out_len, t))
        now = time.perf_counter()
        n_submitted += 1
        if not ok:
            n_rejected += 1
            return
        owner[rid], expect[rid], submit_t[rid] = client, out_len, now
        fed[rid] = len(prompt)  # BOS + all but the prompt's last token
        token_t[rid], token_at[rid] = [], []

    # the ramp: every client starts part-way through a request, so that
    # they do not finish in step
    for c in range(n_clients):
        submit(c, residual=(c + 1) / n_clients)
    initial = set(token_t)

    t_open, long_steps, then = None, [], time.perf_counter()
    while True:
        with run.span("sched.step"):
            events, completions = sched.step()
        now = time.perf_counter()
        if t_open is not None and now - then > 0.25:
            long_steps.append([round(then - t_open, 3), round(now - then, 3)])
        then = now
        for ev in events:
            times = token_t[ev.request_id]
            if not times:
                ttft[ev.request_id] = now - submit_t[ev.request_id]
                if ev.request_id in initial:
                    first_seen.add(ev.request_id)
                else:
                    replaced += 1
            times.append(now)
            token_at[ev.request_id].append(ev.index)
        for c in completions:
            n_done += 1
            if c.n_generated != expect[c.request_id]:
                n_wrong += 1
            submit(owner[c.request_id])
        if t_open is None:
            if len(first_seen) == n_clients and replaced >= t["ramp_completions"]:
                before = (engine.decode_compile_count()
                          + engine.prefill_compile_count())
                m0 = sched.metrics.snapshot()
                done0, sub0 = n_done, n_submitted
                t_open = run.open_window()
        elif run.due():
            break
    t_close = run.close_window()
    gc.callbacks.remove(timed)
    gc.unfreeze()
    if journal is not None:
        journal.close()

    m1 = sched.metrics.snapshot()
    gaps = stats.gaps_in_window(token_t, t_open, t_close)
    run.samples["itl_s"] = gaps
    first_in = [rid for rid, v in ttft.items()
                if t_open < submit_t[rid] + v <= t_close]
    run.samples["ttft_s"] = [ttft[rid] for rid in first_in]
    decoded = [at for rid, times in token_t.items()
               for at, when in zip(token_at[rid], times) if t_open < when <= t_close]
    run.counters.update(
        tokens=stats.tokens_in_window(token_t, t_open, t_close),
        max_slots=t["max_slots"],
        engine_compiles_in_window=(engine.decode_compile_count()
                                   + engine.prefill_compile_count() - before),
        requests_completed=n_done - done0,
        # rows each token of the window saw: a token written at index i was
        # computed by a query at i - 1, which sees i rows in a full layer
        # and min(i, window) in a sliding one; a prompt that fed p
        # positions computed position j from j + 1 of them
        decode_context_sum=sum(decoded),
        decode_window_rows_sum=sum(min(at, window) for at in decoded),
        prefill_context_tokens=sum(fed[r] for r in first_in),
        prefill_context_sum=sum(fed[r] * (fed[r] + 1) // 2 for r in first_in),
        prefill_window_context_sum=sum(window_rows(fed[r], window)
                                       for r in first_in),
        **{k: m1.get(k, 0.0) - m0.get(k, 0.0) for k in COUNTERS},
    )
    run.counters["prefill_real_row_share"] = real_row_share(run.counters, engine)
    for name in GAUGES:
        run.notes[name] = m1.get(name)
    edges = [0.0, 0.008, 0.01, 0.012, 0.015, 0.02, 0.025, 0.03, 0.04, 0.05,
             0.06, 0.08, 0.1, 0.15, 0.2, 0.3, 0.5]
    run.notes["itl_histogram"] = {"edges_s": edges,
                                  "counts": stats.histogram(gaps, edges)}
    # steps over 0.25 s and full collections: [offset in the window, s]
    run.notes["long_steps"] = long_steps
    run.notes["full_collections"] = [
        [round(start - t_open, 3), round(took or 0.0, 4)]
        for start, took in collections if t_open <= start <= t_close]
    attempted = (n_submitted - sub0) + n_clients  # in flight at open + new
    return {
        "correct": check["ok"] and n_wrong == 0 and run.counters["engine_compiles_in_window"] == 0,
        "attempted": attempted, "failed": n_rejected + n_wrong,
        "check": check,
        "compared": compared(
            check, n_wrong, run.counters,
            window_attn_err_over_norm=[check["window_attn_err_over_norm"],
                                       ATTN_TOLERANCE],
            full_attn_err_over_norm=[check["full_attn_err_over_norm"],
                                     ATTN_TOLERANCE],
            routing_slack=[check["routing_slack"], SLACK_TOLERANCE],
            exchanged_share=[check["exchanged_share"], EXCHANGED_TOLERANCE],
            served_tokens_redrawn_at_least=[
                check["served_tokens_redrawn"],
                REDRAWN_AT_LEAST * check["positions"]],
        ),
    }

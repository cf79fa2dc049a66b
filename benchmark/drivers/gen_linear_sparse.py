"""Generation cells of the linear_sparse family (linear-attention layers
with a recurrent state, block-sparse attention layers with an index
cache): the closed loop of ``drivers/gen_latent_moe.py`` over token ids of
the model's own vocabulary, through ``Scheduler`` in front of
``ServeEngine`` built as ``cli/serve.py:_build`` builds them
(``models.build_model`` on the cell's configuration), on seeded bfloat16
weights. The client's own clock around ``Scheduler.step()`` times every
token; the family's counters (rows visible and attended, blocks chosen)
come out of ``ServingMetrics`` like the others.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import stats
from benchmark import traffic as traffic_mod
from benchmark.drivers.gen import compared, make_request, real_row_share
from benchmark.reference import linear_sparse_ref
from progen_tpu.models import build_model
from progen_tpu.sampling import gumbel_step_dynamic

# The served model holds bfloat16 weights and computes in bfloat16 (f32
# accumulation inside a product; the lightning mixer's core, the softmaxes
# and the logits in float32); the reference reads the same weights and
# computes in float32 throughout, at the BLOCKS the system chose at the
# decoded positions (linear_sparse_ref.forward says why). Errors are
# judged against the standard deviation of the reference's logits over
# the 32 decoded positions x 73,448 ids. Every limit stands between two
# chip readings at full width (PR 33, PERF.md section 6; the faults were
# planted under this same function, seed 3300000003):
#   logits - the system, sound: RMS 2.0-2.1%, largest 9.9-10.9%. The
#     reference with every matrix rounded to the 8-bit float (5 exponent
#     bits, 2 of mantissa) below the bfloat16 the configuration states:
#     40% and 217% (and 21.6% exchanged, state 15%); to (4, 3): 122%, 663%.
#     The decay of the next layer: 3.2%, 17%; no forced blocks: 3.6%, 20%
#     - both inside these two limits and seen by the limits below.
#   the recurrent state of the first lightning layer after the prompt,
#     the norm of its error over the norm of the reference's - sound 0.78-0.81%
#     (the keys and values are bfloat16); the reference itself with its
#     state rounded to bfloat16 at every token 1.9%; the decay of the next
#     layer 3.6%; (5, 2) weights 15%. NOT seen: the system keeping its
#     state in bfloat16 (0.79%) - a prefill rounds it once a 512-row
#     block, twenty times in this prompt, and 32 decoded tokens add
#     little; tests/test_linear_sparse.py holds the leaf to float32 and
#     sees the fault at float32 compute instead.
#   choices, which the logits' limits cannot see (the reference is handed
#     them) - how far a chosen free block may stand below the reference's
#     own 31st-best free score (slack: the scores are softmax masses
#     summed over 16 heads), and the share of the 31 free choices a
#     (position, sparse layer, group) that are not the reference's own
#     (exchanged): sound 0.65-1.23e-4 and 1.0-1.5%; no forced blocks 7.4e-4
#     and 64%, with 3,836 forced blocks missing (none is ever allowed);
#     (5, 2) weights 1.5e-3 and 21.6%.
#   the timed program - of the 32 tokens engine.decode_step drew with all
#     16 slots live, gumbel_step_dynamic redraws 28-31 from the logits
#     this check reads alone (another program may round a near-tie the
#     other way); a pool-wide sampler ignoring top_k redraws 0 (PR 28).
RMS_TOLERANCE = 0.05
MAX_TOLERANCE = 0.30
SLACK_TOLERANCE = 4e-4
EXCHANGED_TOLERANCE = 0.05
STATE_TOLERANCE = 0.012
REDRAWN_AT_LEAST = 0.75
LIMITS = (("rms_err_over_std", RMS_TOLERANCE),
          ("max_err_over_std", MAX_TOLERANCE),
          ("selection_slack", SLACK_TOLERANCE),
          ("exchanged_share", EXCHANGED_TOLERANCE),
          ("state_err_over_norm", STATE_TOLERANCE),
          ("forced_blocks_missing", 0))


def build(run):
    """(scheduler, engine, journal) as cli/serve.py:_build makes them."""
    from progen_tpu.serving import Scheduler, ServeEngine
    from progen_tpu.serving.journal import RequestJournal

    t = run.traffic
    model = build_model(run.config)
    params = traffic_mod.seeded_params(model, 8, run.seed, run.devices[0])
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
    cycle (``traffic.paired_cycles``), ids uniform in 1..vocab-1 from the
    seed. The ORDER of the sizes is the cell's own (``size_order_seed``),
    the same in every run: a request lasts about as long as the window
    (1,300 steps of 13-57 ms), so a window holds ONE cycle of 16, and with
    the seed permuting it the runs did different work (15-18 admissions,
    548-576 blocks a window: serve_tok_s_chip spread by 3.4% and itl_p95_s
    by 0.73%, six seeds on the chip, PR 33). The seed still draws every
    id, hence what is selected and attended."""
    pairs = traffic_mod.paired_cycles(
        traffic["prompt_lengths"], traffic["output_lengths"],
        traffic_mod.rng_for(traffic["size_order_seed"], "order"),
    )
    rng = traffic_mod.rng_for(seed, "gen")
    for p_len, out_len in pairs:
        yield rng.integers(1, vocab, size=p_len, dtype=np.int32), out_len


def sparse_layers(cfg: dict) -> list:
    return [f"mix{i}" for i, kind in enumerate(cfg["mixer_types"])
            if kind == linear_sparse_ref.SPARSE]


def check_against_reference(run, engine, reference_kwargs=None) -> dict:
    """Fill the pool through the engine's own chunked admission — the
    checked request (the shortest prompt, ``check_positions`` answers, so
    that every compared position selects) in one slot, grid prompts at
    their own depths in all the others — keep that slot's state as the
    POOL holds it after the prompt, and let the TIMED program,
    ``engine.decode_step``, draw the answer. Then feed that answer through
    the kept state with the served model call (one row; a recurrence
    cannot be fed twice, so not through the pool's own) to read what the
    timed program does not return: the logits and the blocks chosen at
    every decoded position. Comparisons: the logits against the
    reference's full forward over the same tokens; the choices against
    the reference's own scores (it is handed the system's blocks at the
    decoded positions and says which it would not have chosen, by how
    much, and whether a forced block is missing); one lightning layer's
    recurrent state after the prompt against the token recurrence's; and
    the tokens the timed program drew, all slots live, against
    ``gumbel_step_dynamic`` on the logits read alone.
    ``reference_kwargs`` is for a control (a lower precision)."""
    t, cfg = run.traffic, run.config
    rng = traffic_mod.rng_for(run.seed, "check")
    n_prompt, n_check = t["prompt_lengths"][0], t["check_positions"]
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
    kept = jax.tree.map(lambda c: jnp.copy(c[slot]), pool.cache)
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
    names = sparse_layers(cfg)
    lightning = next(f"mix{i}" for i, kind in enumerate(cfg["mixer_types"])
                     if kind == linear_sparse_ref.LIGHTNING)

    @jax.jit
    def decode(params, cache, toks, key, top_k, parity, temp, top_p):
        def one(carry, tok):  # the decode step's model call, one row
            cache, pos, key = carry
            (logits, _), mut = engine.model.apply(
                {"params": params, "cache": cache},
                tok[None, None], pos[None, None], None,
                mutable=["cache", "intermediates"],
            )
            chosen = [mut["intermediates"][name]["blocks"][0][0, :, 0]
                      for name in names]
            logit = logits[0, 0]
            key, drawn = gumbel_step_dynamic(key, logit, top_k, parity,
                                             temp, top_p)
            # the template's rule: a drawn EOS becomes the best other id
            drawn = jnp.where(drawn == 0, jnp.argmax(logit[1:]) + 1, drawn)
            return (mut["cache"], pos + 1, key), (logit, jnp.stack(chosen), drawn)

        return jax.lax.scan(one, (cache, jnp.int32(n_prompt), key), toks)[1]

    # the prefill fed row[0:n_prompt]; decoding feeds row[n_prompt:-1]
    state = np.asarray(kept[lightning]["state"][0], np.float32)
    got, chosen, drawn = decode(engine.params, kept,
                                jnp.asarray(row[n_prompt:-1]), *knobs)
    del kept
    topk = chosen.shape[-1]
    own = np.full((n_prompt, cfg["num_key_value_heads"], topk), -1, np.int32)
    want, aux = linear_sparse_ref.forward(
        engine.params, jnp.asarray(row[:-1]), cfg, return_aux=True,
        logits_from=n_prompt, state_at=n_prompt,
        blocks=[np.concatenate([own, np.asarray(chosen[:, i])])
                for i in range(len(names))],
        **(reference_kwargs or {}),
    )
    check = stats.relative_errors(got, want)
    slack = np.stack([np.asarray(s)[n_prompt:] for s in aux["slack"]])
    free = topk - cfg["sparse_config"]["init_blocks"] - (
        cfg["sparse_config"]["window_size"] // cfg["sparse_config"]["block_size"])
    check["selection_slack"] = float(slack.max())
    # of the free choices (the forced ones are no choice)
    check["exchanged_share"] = float((slack > 0).sum() / max(
        slack.shape[0] * slack.shape[1] * slack.shape[2] * free, 1))
    check["forced_blocks_missing"] = int(sum(
        np.asarray(m)[n_prompt:].sum() for m in aux["forced_missing"]))
    ref_state = np.asarray(aux["state"][0], np.float32)
    check["state_err_over_norm"] = float(
        np.linalg.norm(state - ref_state) / np.linalg.norm(ref_state))
    check["served_tokens_redrawn"] = int(
        (np.asarray(drawn) == row[n_prompt + 1:]).sum()
    )
    return {"positions": int(got.shape[0]), **check,
            "tolerances": [RMS_TOLERANCE, MAX_TOLERANCE, SLACK_TOLERANCE,
                           EXCHANGED_TOLERANCE, STATE_TOLERANCE,
                           REDRAWN_AT_LEAST],
            "ok": all_live
            and all(check[name] <= limit for name, limit in LIMITS)
            and check["served_tokens_redrawn"]
            >= REDRAWN_AT_LEAST * got.shape[0]}


COUNTERS = (
    "decode_steps", "decode_steps_ahead", "decode_tokens", "prefill_tokens",
    "prefill_blocks", "prefill_time_s", "decode_time_s",
    "sparse_layer_steps", "sparse_rows_visible", "sparse_rows_attended",
    "sparse_blocks_selected", "sparse_feed_layer_blocks",
    "sparse_feed_rows_visible", "sparse_feed_rows_attended",
    "sparse_feed_blocks_selected",
)
GAUGES = ("kv_cache_bytes", "index_cache_bytes", "linear_state_bytes",
          "served_weight_bytes")


def run(run) -> dict:
    t = run.traffic
    sched, engine, journal = build(run)
    vocab = engine.model.config.num_tokens
    check = check_against_reference(run, engine)
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

    t_open = None
    while True:
        with run.span("sched.step"):
            events, completions = sched.step()
        now = time.perf_counter()
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
    if journal is not None:
        journal.close()

    m1 = sched.metrics.snapshot()
    gaps = stats.gaps_in_window(token_t, t_open, t_close)
    run.samples["itl_s"] = gaps
    first_in = [rid for rid, v in ttft.items()
                if t_open < submit_t[rid] + v <= t_close]
    run.samples["ttft_s"] = [ttft[rid] for rid in first_in]
    run.counters.update(
        tokens=stats.tokens_in_window(token_t, t_open, t_close),
        max_slots=t["max_slots"],
        engine_compiles_in_window=(engine.decode_compile_count()
                                   + engine.prefill_compile_count() - before),
        requests_completed=n_done - done0,
        # positions each token of the window saw, as the other cells keep
        # them (this family's yardsticks count from the program's own
        # counters of rows visible and attended instead)
        decode_context_sum=sum(
            at for rid, times in token_t.items()
            for at, when in zip(token_at[rid], times) if t_open < when <= t_close
        ),
        prefill_context_sum=sum(fed[r] * (fed[r] + 1) // 2 for r in first_in),
        **{k: m1.get(k, 0.0) - m0.get(k, 0.0) for k in COUNTERS},
    )
    run.counters["prefill_real_row_share"] = real_row_share(run.counters, engine)
    for name in GAUGES:
        run.notes[name] = m1.get(name)
    edges = [0.0, 0.008, 0.01, 0.012, 0.015, 0.02, 0.025, 0.03, 0.04, 0.05,
             0.06, 0.08, 0.1, 0.15, 0.2, 0.3, 0.5]
    run.notes["itl_histogram"] = {"edges_s": edges,
                                  "counts": stats.histogram(gaps, edges)}
    attempted = (n_submitted - sub0) + n_clients  # in flight at open + new
    return {
        "correct": check["ok"] and n_wrong == 0 and run.counters["engine_compiles_in_window"] == 0,
        "attempted": attempted, "failed": n_rejected + n_wrong,
        "check": check,
        "compared": compared(
            check, n_wrong, run.counters,
            selection_slack=[check["selection_slack"], SLACK_TOLERANCE],
            exchanged_share=[check["exchanged_share"], EXCHANGED_TOLERANCE],
            forced_blocks_missing=[check["forced_blocks_missing"], 0],
            state_err_over_norm=[check["state_err_over_norm"], STATE_TOLERANCE],
            served_tokens_redrawn_at_least=[
                check["served_tokens_redrawn"],
                REDRAWN_AT_LEAST * check["positions"]],
        ),
    }

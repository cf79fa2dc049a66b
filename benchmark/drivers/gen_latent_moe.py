"""Generation cells of the latent-attention, routed-expert family: the
closed loop of ``drivers/gen.py`` over token ids of the model's own
vocabulary, through ``Scheduler`` in front of ``ServeEngine`` built as
``cli/serve.py:_build`` builds them (``models.build_model`` on the cell's
configuration), on seeded bfloat16 weights. The client's own clock around
``Scheduler.step()`` times every token; the family's counters (experts
touched, busiest expert) come out of ``ServingMetrics`` like the others.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import stats
from benchmark import traffic as traffic_mod
from benchmark.drivers.gen import compared, make_request, real_row_share
from benchmark.reference import latent_moe_ref
from progen_tpu.models import build_model  # a program without it fails here
from progen_tpu.sampling import gumbel_step_dynamic

# The served model holds bfloat16 weights and computes in bfloat16 (f32
# accumulation inside a product; f32 router, softmax and logits); the
# reference reads the same weights and computes in float32 throughout,
# at the decisions the system made (latent_moe_ref.forward says why: at
# its own decisions the system read RMS 10-17% and largest 134-166% on
# the chip, all of it exchanged experts). Errors are judged against the
# standard deviation of the reference's logits over the 32 decoded
# positions x 128,256 ids. Every limit stands between two chip readings
# at full width (PR 28, PERF.md section 6; the faults were planted under
# this same function, seeds 2800000201-203):
#   logits - the system, 27 seeds: RMS 1.50-1.76%, largest 7.8-9.9%; the
#     same as the reference itself computed in bfloat16 (1.65-1.70%,
#     8.9-9.0%). The reference with its experts held in int8 (a scale per
#     output channel) against its float32 self: RMS 7.0%, largest 90%;
#     with every matrix rounded to a 9-bit float (5 exponent bits, 3 of
#     mantissa): 13.9-14.4%, 78-84%; to the 8-bit float (5, 2) below the
#     bfloat16 the configuration states: 30.6-32.4%, 163-190%. A wrong
#     cache row, a dropped expert or an unscaled weight reads near 100%.
#   decisions, which the logits' limits cannot see (the reference is
#     handed them) - how far an expert the system used may stand below the
#     reference's own 6th best score (slack), and the share of (position,
#     expert layer) choices that are not the reference's own (exchanged):
#     the system 0.0032-0.0074 and 5.8-9.8% (ties that the bfloat16
#     rounding of the router's input turns); routing as if the checkpoint
#     had no bias 0.026-0.032 and 41-46%; the 7th best taken for the 6th
#     0.064 and 91%; one wrong expert of six 0.72 and 97%. NOT seen: a
#     router whose product and sigmoid are rounded to bfloat16 (0.0024,
#     5.4%): that rounding moves a score by less than the bfloat16 input
#     already does; tests/test_latent_moe.py holds the router's arithmetic
#     to float32 by its jaxpr instead.
#   the timed program - of the 32 tokens engine.decode_step drew with all
#     32 slots live, gumbel_step_dynamic redraws 31-32 from the logits
#     this check reads alone (another program may round a near-tie the
#     other way); with the pool-wide sampler ignoring top_k it redraws 0.
RMS_TOLERANCE = 0.05
MAX_TOLERANCE = 0.30
SLACK_TOLERANCE = 0.014
EXCHANGED_TOLERANCE = 0.2
REDRAWN_AT_LEAST = 0.75


def seeded_params(model, seed: int, device):
    """The model's parameters from the seed, made on the device in the
    type the configuration states, with ``e_score_correction_bias`` drawn
    too (the family initialises it to zero, which would leave the bias'
    part in choosing experts untested)."""
    params = traffic_mod.seeded_params(model, 8, seed, device)
    key = traffic_mod.fold_key(seed + 1)

    def draw(path, leaf):
        if "e_score_correction_bias" not in jax.tree_util.keystr(path):
            return leaf
        k = jax.random.fold_in(key, sum(jax.tree_util.keystr(path).encode()))
        return 0.01 * jax.random.normal(k, leaf.shape, leaf.dtype)

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
    cycle (``traffic.paired_cycles``), ids uniform in 1..vocab-1."""
    rng = traffic_mod.rng_for(seed, "gen")
    pairs = traffic_mod.paired_cycles(
        traffic["prompt_lengths"], traffic["output_lengths"], rng
    )
    for p_len, out_len in pairs:
        yield rng.integers(1, vocab, size=p_len, dtype=np.int32), out_len


def check_against_reference(run, engine, reference_params=None) -> dict:
    """Fill the pool through the engine's own chunked admission — the
    checked request (the shortest prompt, ``check_positions`` answers) in
    one slot, grid prompts at their own depths in all the others — and
    let the TIMED program, ``engine.decode_step``, draw the answer. Then
    feed that answer again through the pool's cache with the served
    model call (all slots one batch, this one live) to read what the
    timed program does not return: the logits and the experts chosen at
    every decoded position. Three comparisons: the logits against the
    reference's full forward pass over the same tokens; the choices
    against the reference's own scores (it is handed the system's experts
    at the decoded positions, ``latent_moe_ref.forward`` has the reason,
    and says how many it would not have chosen and by how much); and the
    tokens the timed program drew, all 32 slots live, against
    ``gumbel_step_dynamic`` on the logits read alone. ``reference_params``
    is for a control that plants a fault in the system's weights."""
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
    moe = [f"ffn{i}" for i in range(cfg["first_k_dense_replace"],
                                    cfg["num_hidden_layers"])]

    @jax.jit
    def decode(params, cache, toks, key, top_k, parity, temp, top_p):
        def one(carry, tok):  # LatentMoE.decode_slots, keeping the choices
            cache, pos, key = carry
            (logits, _), mut = engine.model.apply(
                {"params": params,
                 "cache": jax.tree.map(lambda c: c[:, 0], cache)},
                jnp.full((n_slots, 1), tok), jnp.full((n_slots, 1), pos),
                live, mutable=["cache", "intermediates"],
            )
            chosen = [mut["intermediates"][name]["experts"][0][slot]
                      for name in moe]
            cache = jax.tree.map(lambda c: c[:, None], mut["cache"])
            logit = logits[slot, 0]
            key, drawn = gumbel_step_dynamic(key, logit, top_k, parity,
                                             temp, top_p)
            # the template's rule: a drawn EOS becomes the best other id
            drawn = jnp.where(drawn == 0, jnp.argmax(logit[1:]) + 1, drawn)
            return (cache, pos + 1, key), (logit, jnp.stack(chosen), drawn)

        return jax.lax.scan(one, (cache, jnp.int32(n_prompt), key), toks)[1]

    # the prefill fed row[0:n_prompt]; decoding feeds row[n_prompt:-1]:
    # the rows the timed steps wrote at those positions are written again
    got, chosen, drawn = decode(engine.params, engine.slots.cache,
                                jnp.asarray(row[n_prompt:-1]), *knobs)
    own = np.full((n_prompt, cfg["num_experts_per_tok"]), -1, np.int32)
    want, routing = latent_moe_ref.forward(
        engine.params if reference_params is None else reference_params,
        jnp.asarray(row[:-1]), cfg, return_routing=True,
        experts=[np.concatenate([own, np.asarray(chosen[:, i])])
                 for i in range(len(moe))],
    )
    check = stats.relative_errors(got, want[n_prompt:])
    slack = np.stack([np.asarray(s)[n_prompt:] for s in routing["slack"]])
    check["routing_slack"] = float(slack.max())
    check["exchanged_share"] = float((slack > 0).mean())
    check["served_tokens_redrawn"] = int(
        (np.asarray(drawn) == row[n_prompt + 1:]).sum()
    )
    return {"positions": int(got.shape[0]), **check,
            "tolerances": [RMS_TOLERANCE, MAX_TOLERANCE, SLACK_TOLERANCE,
                           EXCHANGED_TOLERANCE, REDRAWN_AT_LEAST],
            "ok": all_live
            and check["rms_err_over_std"] <= RMS_TOLERANCE
            and check["max_err_over_std"] <= MAX_TOLERANCE
            and check["routing_slack"] <= SLACK_TOLERANCE
            and check["exchanged_share"] <= EXCHANGED_TOLERANCE
            and check["served_tokens_redrawn"]
            >= REDRAWN_AT_LEAST * got.shape[0]}


COUNTERS = (
    "decode_steps", "decode_steps_ahead", "decode_tokens", "prefill_tokens",
    "prefill_blocks", "prefill_time_s", "decode_time_s", "moe_expert_layer_steps",
    "moe_assignments", "moe_experts_touched", "moe_max_load_rows",
    "moe_feed_expert_layer_blocks", "moe_feed_experts_touched",
    "moe_feed_max_load_rows",
)


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
    # through validation now: the program pads each prime with a jitted
    # jnp.pad, one tiny compile per shape, which would otherwise fall
    # inside the window (PERF.md, Open questions)
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
        # positions each token of the window saw: a token written at
        # index i was computed from positions 0..i-1; a prompt that fed p
        # positions computed position j from j + 1 of them
        decode_context_sum=sum(
            at for rid, times in token_t.items()
            for at, when in zip(token_at[rid], times) if t_open < when <= t_close
        ),
        prefill_context_sum=sum(fed[r] * (fed[r] + 1) // 2 for r in first_in),
        **{k: m1.get(k, 0.0) - m0.get(k, 0.0) for k in COUNTERS},
    )
    run.counters["prefill_real_row_share"] = real_row_share(run.counters, engine)
    run.counters["moe_mean_load_rows"] = (
        run.counters["moe_assignments"] / run.config["n_routed_experts"]
    )
    run.notes["latent_cache_bytes"] = m1.get("latent_cache_bytes")
    edges = [0.0, 0.015, 0.02, 0.025, 0.03, 0.04, 0.05, 0.06, 0.08, 0.1,
             0.15, 0.2, 0.3, 0.5]
    run.notes["itl_histogram"] = {"edges_s": edges,
                                  "counts": stats.histogram(gaps, edges)}
    attempted = (n_submitted - sub0) + n_clients  # in flight at open + new
    return {
        "correct": check["ok"] and n_wrong == 0 and run.counters["engine_compiles_in_window"] == 0,
        "attempted": attempted, "failed": n_rejected + n_wrong,
        "check": check,
        "compared": compared(
            check, n_wrong, run.counters,
            routing_slack=[check["routing_slack"], SLACK_TOLERANCE],
            exchanged_share=[check["exchanged_share"], EXCHANGED_TOLERANCE],
            served_tokens_redrawn_at_least=[
                check["served_tokens_redrawn"],
                REDRAWN_AT_LEAST * check["positions"]],
        ),
    }

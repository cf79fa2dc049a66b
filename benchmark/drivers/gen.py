"""Generation cells: a closed loop of clients over the served path —
``Scheduler`` in front of ``ServeEngine``, built as ``cli/serve.py:_build``
builds them, but on seeded weights. The client's own clock around
``Scheduler.step()`` times every token."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import stats
from benchmark import traffic as traffic_mod
from benchmark.counts import progen as counts
from benchmark.reference import progen_ref

# The served model computes in bfloat16 from float32 weights; the
# reference is float32 throughout. One bf16 rounding is 2^-9 relative and a
# logit passes through 2 x depth residual blocks, so errors are judged
# against the standard deviation of the reference's logits: root-mean-
# square and largest. Measured on the chip at full width (PR 24, 14 runs,
# 7 seeds): RMS 4.1-4.4%, largest 15-19%, the same in every run of a seed.
# The tolerances are about twice that. A wrong cache position, a missing
# phantom-key correction or a dropped layer decorrelates the logits (RMS
# ratio near 1.4); per-channel int8 weights round about five times as
# coarsely as bf16.
RMS_TOLERANCE = 0.08
MAX_TOLERANCE = 0.35


def build(run):
    """(scheduler, engine, journal) as cli/serve.py:_build makes them."""
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import Scheduler, ServeEngine
    from progen_tpu.serving.journal import RequestJournal

    t = run.traffic
    config = ProGenConfig.from_dict(run.config)
    model = ProGen(config)
    # weights on the device in one jitted call, already in the unrolled
    # layout the decoder serves (a checkpoint's stacked layout would be
    # unstacked by the engine into a second full copy)
    params = traffic_mod.seeded_params(
        ProGen(dataclasses.replace(config, scan_layers=False)),
        config.seq_len, run.seed, run.devices[0],
    )
    with jax.default_device(run.devices[0]):
        engine = ServeEngine(model, params, max_slots=t["max_slots"],
                             max_len=t["max_len"])
    journal = None
    if t["journal"]:
        journal = RequestJournal(run.tmp / "journal" / "journal.jsonl")
    sched = Scheduler(engine, max_queue=t["max_queue"], journal=journal,
                      prefill_chunk=t["prefill_chunk"])
    return sched, engine, journal


def make_request(rid: str, prompt: np.ndarray, out_len: int, t: dict):
    """A template request that freezes the prompt and leaves ``out_len``
    free positions: the engine's infilling rule replaces an EOS drawn at a
    free position, so exactly ``out_len`` tokens come back."""
    from progen_tpu.serving import Request

    length = 1 + len(prompt) + out_len  # BOS + prompt + output
    template = np.zeros((length,), np.int32)
    frozen = np.zeros((length,), bool)
    template[1:1 + len(prompt)] = prompt
    frozen[1:1 + len(prompt)] = True
    return Request(
        id=rid, prime=prompt, length=length, add_bos=True,
        top_k=t["top_k"], temperature=t["temperature"],
        seed=int(prompt[:4].sum()), template=template, frozen=frozen,
    )


def real_row_share(counters: dict, engine):
    """Share of the rows the window's prefill passes computed that fed a
    prompt: a pass computes a whole block of the engine's width."""
    rows = counters["prefill_blocks"] * engine.prefill_width
    return counters["prefill_tokens"] / rows if rows else None


def compared(check: dict, n_wrong: int, counters: dict, **more) -> dict:
    """Each number that decides ``correct`` beside its limit."""
    return {
        "logits_rms_over_std": [check["rms_err_over_std"], check["tolerances"][0]],
        "logits_max_over_std": [check["max_err_over_std"], check["tolerances"][1]],
        **more,
        "wrong_output_lengths": [n_wrong, 0],
        "compiles_in_window": [counters["engine_compiles_in_window"], 0],
    }


def check_against_reference(run, engine) -> dict:
    """Prefill a prompt through the engine's own chunked admission, then
    decode through the cache with the served decode model, feeding known
    tokens; compare the logits at every decoded position with the
    reference's full forward pass over the same tokens."""
    t, cfg = run.traffic, run.config
    rng = traffic_mod.rng_for(run.seed, "check")
    n_prompt, n_check = t["prompt_lengths"][0], t["check_positions"]
    tokens = rng.integers(1, 256, size=n_prompt + n_check, dtype=np.int32)
    row = np.concatenate([[0], tokens]).astype(np.int32)  # BOS first

    slot = engine.acquire()
    pending = engine.begin_prefill(
        slot, tokens[:n_prompt], 1 + n_prompt + n_check, add_bos=True,
        top_k=t["top_k"],
    )
    while not engine.advance_prefill(pending, t["prefill_chunk"]):
        pass
    cache = jax.tree.map(lambda c: c[slot], engine.slots.cache)
    engine.release(slot)

    @jax.jit
    def decode(params, cache, toks):
        def one(cache, tok):
            logits, mut = engine.model.apply(
                {"params": params, "cache": cache}, tok[None, None],
                mutable=["cache"],
            )
            return mut["cache"], logits[0, 0]

        return jax.lax.scan(one, cache, toks)[1]

    # the prefill fed row[0:n_prompt]; decoding feeds row[n_prompt:]
    got = decode(engine.params, cache, jnp.asarray(row[n_prompt:-1]))
    want = jax.jit(lambda p, r: progen_ref.forward(p, r, cfg))(
        engine.params, jnp.asarray(row[:-1])
    )[n_prompt:]
    check = stats.relative_errors(got, want)
    return {"positions": int(got.shape[0]), **check,
            "tolerances": [RMS_TOLERANCE, MAX_TOLERANCE],
            "ok": check["rms_err_over_std"] <= RMS_TOLERANCE
            and check["max_err_over_std"] <= MAX_TOLERANCE}


def run(run) -> dict:
    t = run.traffic
    sched, engine, journal = build(run)
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

    requests = traffic_mod.gen_requests(t, run.seed)
    n_clients = t["clients"]
    owner, expect, submit_t, fed = {}, {}, {}, {}
    token_t, first_at, ttft = {}, {}, {}
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
        token_t[rid] = []

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
                first_at[ev.request_id] = ev.index  # the rest follow it
                if ev.request_id in initial:
                    first_seen.add(ev.request_id)
                else:
                    replaced += 1
            times.append(now)
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
    # positions each token of the window saw (benchmark/counts/progen.py
    # counts from them): the k-th token of a request is written at index
    # first_at + k by the query one position before it, which mixes every
    # position up to itself and attends to the rows of its window; a
    # prompt that fed p positions computed position j likewise
    cfg = run.config
    at = [first_at[rid] + k for rid, times in token_t.items()
          for k, when in enumerate(times) if t_open < when <= t_close]
    run.counters.update(
        decode_context_sum=sum(at),
        decode_window_rows_sum=sum(counts.window_rows(cfg, i - 1) for i in at),
        prefill_context_sum=sum(fed[r] * (fed[r] + 1) // 2 for r in first_in),
        prefill_window_rows_sum=sum(
            counts.window_rows(cfg, j) for r in first_in for j in range(fed[r])
        ),
    )
    run.counters.update(
        tokens=stats.tokens_in_window(token_t, t_open, t_close),
        max_slots=t["max_slots"],
        engine_compiles_in_window=(engine.decode_compile_count()
                                   + engine.prefill_compile_count() - before),
        requests_completed=n_done - done0,
        **{k: m1.get(k, 0.0) - m0.get(k, 0.0) for k in
           ("decode_steps", "decode_steps_ahead", "decode_tokens",
            "prefill_tokens", "prefill_blocks", "prefill_time_s",
            "decode_time_s")},
    )
    run.counters["prefill_real_row_share"] = real_row_share(run.counters, engine)
    edges = [0.0, 0.02, 0.03, 0.04, 0.06, 0.08, 0.1, 0.12, 0.15, 0.2, 0.3, 0.5]
    run.notes["itl_histogram"] = {"edges_s": edges,
                                  "counts": stats.histogram(gaps, edges)}
    attempted = (n_submitted - sub0) + n_clients  # in flight at open + new
    return {
        "correct": check["ok"] and n_wrong == 0 and run.counters["engine_compiles_in_window"] == 0,
        "attempted": attempted, "failed": n_rejected + n_wrong,
        "check": check,
        "compared": compared(check, n_wrong, run.counters),
    }

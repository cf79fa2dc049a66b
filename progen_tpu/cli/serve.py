"""Serving CLI — continuous-batching engine loop against a checkpoint.

Runs the slot-pool engine (progen_tpu/serving/) as a single-threaded
event loop. Requests arrive as JSON lines, one object per request:

    {"id": "r1", "prime": "[tax=Mammalia] #", "length": 256,
     "temperature": 0.8, "top_p": 0.95, "top_k": 25, "seed": 7}

(``id`` and ``prime`` required; everything else optional — ``length``
defaults to --max-len.) ``"tokens": [ids]`` may stand in place of
``prime``: raw token ids, the only way in to a model family without a
byte codec (``latent_moe``), which answers ids too — its token events
carry no ``text`` and its done event ``tokens`` in place of ``text``. The router's resume wire (serving/router.py)
uses three extra optional fields: ``prime_tokens`` (raw token ids,
bypassing the tokenizer), ``key`` (explicit uint32 PRNG key pair) and
``add_bos`` (default true) — together they let a handed-off request
continue bit-identically on another replica.

Two protein-design request shapes ride the same wire
(progen_tpu/workloads/):

    {"id": "f1", "template": "MK?LV??G", "free_char": "?", ...}
    {"id": "e1", "prime": "[tax=Mammalia] # MKLV", "embed": true}

``template`` is fixed-position infilling: frozen characters are kept
verbatim, ``free_char`` slots (default "?") are sampled; the leading
frozen run becomes the prime and ``length`` is the template's, so both
are derived, not read. (The resume wire may instead carry buffer-
aligned ``template_tokens`` + ``frozen`` lists.) ``embed: true`` asks
for a mean-pooled final-norm embedding of the prime instead of
generation — the reply is a single terminal ``embedding`` event.
Responses stream back as JSON lines, one per
event, interleaved across requests as the engine produces them:

    {"event": "token", "id": "r1", "token": 77, "text": "L", "index": 18}
    {"event": "done", "id": "r1", "text": "...", "n_generated": 238,
     "ttft_s": 0.01, "latency_s": 0.9}
    {"event": "embedding", "id": "e1", "dim": 1024, "values": [...],
     "latency_s": 0.02}
    {"event": "rejected", "id": "r9", "reason": "queue_full"}

Three transports, same protocol:
  * default: requests on stdin, events on stdout (pipe-friendly;
    EOF drains the queue and exits);
  * --socket PATH: a unix domain socket server; each connection
    submits requests and receives exactly its own events;
  * --tcp HOST:PORT: the same server over framed TCP
    (fleet/transport.py — every frame's payload is exactly one of
    these JSONL lines, so streams are bit-identical to the unix
    transport and journal/replay/handoff work unchanged).

Connection-oriented transports also answer a control line,
``{"ctl": "release", "id": ...}`` — the router's rebalance/scale-down
path asking this replica to surrender one still-queued request
(``{"event": "released", "released": true|false}``; a granted release
is journaled ``done(handed_off)`` so --replay skips it).

Zero-downtime ops (see README "Zero-downtime ops"):
  * SIGHUP hot-reloads the newest verified checkpoint in a background
    thread and swaps it in between decode steps — zero recompiles,
    zero dropped requests; ``--reload_watch N`` polls the checkpoint
    dir every N seconds and reloads automatically;
  * ``--journal_dir DIR`` journals accepted requests + emitted tokens
    to DIR/journal.jsonl; after a crash, ``--replay DIR`` resumes every
    unfinished accepted request bit-identically (dedup on request id —
    completed work is never re-emitted).

Run: python -m progen_tpu.cli.serve --max-slots 8 --max-queue 64
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import json
import os
import select
import socket
import sys

import click
import numpy as np


def _parse_request(line, defaults):
    """JSONL line -> (Request, error_string). Tokenizes the prime and
    applies server defaults; malformed input becomes a rejection event
    rather than a crash (a server must outlive its worst client)."""
    from progen_tpu.data.tokenizer import encode_tokens
    from progen_tpu.serving import Request

    try:
        obj = json.loads(line)
        if not isinstance(obj, dict):
            raise ValueError("request must be a JSON object")
        rid = str(obj["id"])
    except (ValueError, KeyError) as e:
        return None, f"bad request line: {e}"
    try:
        raw = obj.get("prime_tokens", obj.get("tokens"))
        if raw is not None:
            # raw token ids: a client of a family without a byte codec,
            # or the router's resume wire (already-tokenized prefix of a
            # handed-off request) — bypasses the tokenizer
            prime = np.asarray([int(t) for t in raw], dtype=np.int32)
        elif not defaults.get("byte_codec", True):
            raise ValueError(
                'this model has no byte codec: send "tokens": [ids] '
                'in place of "prime" or "template"'
            )
        else:
            prime = np.asarray(
                encode_tokens(str(obj.get("prime", ""))), dtype=np.int32
            )
        key = None
        if obj.get("key") is not None:
            # explicit PRNG key (raw uint32 pair): resumed requests must
            # continue the EXACT stream, not restart a seed
            import jax.numpy as jnp

            key = jnp.asarray(
                [int(k) for k in obj["key"]], dtype=jnp.uint32
            )
        add_bos = bool(obj.get("add_bos", True))
        length = int(obj.get("length", defaults["length"]))
        template = frozen = None
        if obj.get("template") is not None:
            # infilling: the template fixes prime AND length — frozen
            # prefix is the prime, template width is the length
            from progen_tpu.workloads.infill import (
                infill_request_arrays,
                parse_template,
            )

            toks, frz = parse_template(
                str(obj["template"]), str(obj.get("free_char", "?"))
            )
            prime, length, template, frozen = infill_request_arrays(
                toks, frz, add_bos=add_bos
            )
        elif obj.get("template_tokens") is not None:
            # resume wire: buffer-aligned constraint arrays as journaled
            # (prime/length/add_bos already carried by their own fields)
            template = np.asarray(
                [int(t) for t in obj["template_tokens"]], dtype=np.int32
            )
            frozen = np.asarray(
                [bool(f) for f in obj.get("frozen", [])], dtype=bool
            )
        req = Request(
            id=rid,
            prime=prime,
            length=length,
            kind="embed" if obj.get("embed") else "generate",
            template=template,
            frozen=frozen,
            top_k=(None if obj.get("top_k", defaults["top_k"]) is None
                   else int(obj.get("top_k", defaults["top_k"]))),
            # default True: server parity with cli/sample.py; resumed
            # requests carry their journaled add_bos explicitly
            add_bos=add_bos,
            temperature=float(
                obj.get("temperature", defaults["temperature"])
            ),
            top_p=(None if obj.get("top_p", defaults["top_p"]) is None
                   else float(obj.get("top_p", defaults["top_p"]))),
            seed=int(obj.get("seed", defaults["seed"])),
            key=key,
            deadline_s=(None if obj.get("deadline_s") is None
                        else float(obj["deadline_s"])),
            # cross-process trace context minted by the router (or an
            # upstream client): stamped on this replica's req records
            # and journaled, so the fleet trace stays one journey
            trace_id=(None if obj.get("trace_id") is None
                      else str(obj["trace_id"])),
        )
        return req, None
    except (ValueError, TypeError) as e:
        # keep the id so the rejection can still be routed to its request
        return (
            Request(id=rid, prime=np.zeros(0, np.int32), length=-1),
            f"bad request fields: {e}",
        )


def _as_text(tokens, defaults) -> dict:
    """The field that carries generated tokens to the client: text, or
    the ids themselves for a family without a byte codec
    (``defaults["byte_codec"]``, set once by main())."""
    from progen_tpu.data.tokenizer import decode_tokens

    if defaults.get("byte_codec", True):
        return {"text": decode_tokens(tokens)}
    return {"tokens": [int(t) for t in tokens]}


def _events_to_lines(events, completions, starts, defaults):
    """Engine step output -> protocol JSONL strings. ``starts`` maps
    request id -> primed positions, so done-events can report only the
    generated suffix as text (parity with sample.py's print)."""
    lines = []
    for ev in events:
        line = {"event": "token", "id": ev.request_id,
                "token": int(ev.token)}
        if defaults.get("byte_codec", True):
            # without a codec, "token" has said it all
            line.update(_as_text([ev.token], defaults))
        line["index"] = int(ev.index)
        lines.append(json.dumps(line))
    for c in completions:
        start = starts.pop(c.request_id, 0)
        if getattr(c, "embedding", None) is not None:
            # embed requests terminate with the vector, not a done line
            vec = c.embedding
            lines.append(json.dumps({
                "event": "embedding",
                "id": c.request_id,
                "dim": int(vec.shape[0]),
                "values": [round(float(x), 6) for x in vec],
                "latency_s": round(c.latency_s, 6),
            }))
            continue
        lines.append(json.dumps({
            "event": "done",
            "id": c.request_id,
            **_as_text(c.tokens[start:], defaults),
            "n_generated": int(c.n_generated),
            "ttft_s": round(c.ttft_s, 6),
            "latency_s": round(c.latency_s, 6),
        }))
    return lines


def _build(checkpoint_path, max_slots, max_len, max_queue,
           quantize_int8=False, journal=None, prefill_chunk=0,
           prefix_cache_mb=0, pin=None):
    import os.path

    from progen_tpu.checkpoint import get_checkpoint_fns
    from progen_tpu.models import build_model
    from progen_tpu.serving import PrefixCache, Scheduler, ServeEngine

    _, get_last, _ = get_checkpoint_fns(checkpoint_path)
    pkg = None
    if pin is not None:
        # a pre-existing pin file names the checkpoint this replica must
        # serve (a controller-managed fleet member rebooting mid-deploy);
        # an unloadable pin falls back to newest — the replica must come
        # up serving SOMETHING, and the ack tells the controller the pin
        # was not honored
        pkg = get_last.restore_params(at=pin)
        if pkg is None:
            print(
                f"reload pin {pin}: not restorable, falling back to "
                f"newest checkpoint", file=sys.stderr,
            )
    if pkg is None:
        pkg = get_last.restore_params()
    if pkg is None:
        sys.exit(f"no checkpoints found at {checkpoint_path}")
    model = build_model(pkg.model_config)
    config = model.config
    engine = ServeEngine(
        model, pkg.state, max_slots=max_slots,
        max_len=min(max_len or config.seq_len, config.seq_len),
        quantize_int8=quantize_int8,
    )
    if engine.quant_report is not None:
        r = engine.quant_report
        print(
            f"int8 weights: {r['quantized_leaves']} kernels, "
            f"{r['bytes_fp']} -> {r['bytes_int8']} bytes, "
            f"calib logits max-abs-err {r['logits_max_abs_err']:.3g}",
            file=sys.stderr,
        )
    ckpt_name = os.path.basename(pkg.path) if pkg.path else None
    prefix_cache = None
    if prefix_cache_mb:
        prefix_cache = PrefixCache(int(prefix_cache_mb) * (1 << 20))
    sched = Scheduler(engine, max_queue=max_queue, journal=journal,
                      prefill_chunk=prefill_chunk,
                      prefix_cache=prefix_cache)
    return sched, engine, ckpt_name


@click.command()
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--max-slots", default=8,
              help="device decode lanes: concurrent requests advanced "
                   "per step (fixes the compiled shapes)")
@click.option("--max-queue", default=64,
              help="bounded admission queue; submits beyond this are "
                   "rejected with reason 'queue_full'")
@click.option("--max-len", default=None, type=int,
              help="longest servable sequence (default: the model's "
                   "seq_len); also the per-request 'length' default")
@click.option("--int8/--no-int8", "quantize_int8", default=False,
              help="serve int8 weight-quantized matmuls (per-channel "
                   "symmetric, dequant fused on-device); logs a "
                   "max-abs-error calibration report at load")
@click.option("--prefill_chunk", default=0,
              help="admit long prompts N prime tokens per decode step "
                   "(chunked prefill) instead of stalling every live "
                   "decode for the whole prompt; 0 = no budget, the "
                   "whole prime as one chunk. Streams are bit-identical "
                   "either way")
@click.option("--prefix_cache_mb", default=0,
              help="LRU cache of prefill-state snapshots keyed on the "
                   "token-prefix hash, in MiB of device cache bytes "
                   "(0 = off): repeated scaffolds skip their shared "
                   "prefix at admission. Invalidated on hot reload")
@click.option("--top_k", default=25, help="default per-request top_k")
@click.option("--temperature", default=1.0,
              help="default per-request temperature")
@click.option("--top_p", default=None, type=float,
              help="default per-request nucleus mass")
@click.option("--seed", default=42, help="default per-request PRNG seed")
@click.option("--socket", "socket_path", default=None, type=str,
              help="serve a unix domain socket at PATH instead of "
                   "stdin/stdout")
@click.option("--tcp", "tcp_hostport", default=None, type=str,
              help="serve framed TCP at HOST:PORT (fleet transport: "
                   "length-prefixed frames whose payloads are exactly "
                   "the JSONL protocol lines; PORT 0 = ephemeral, the "
                   "bound port is printed on stderr)")
@click.option("--idle_timeout", default=0.0, type=float,
              help="drop a --tcp peer silent for more than N seconds "
                   "(0 = never; unix sockets never need this, half-open "
                   "TCP peers hold sockets forever)")
@click.option("--metrics-every", default=0,
              help="log a serve/ metrics snapshot to the tracker (and "
                   "rewrite --prom_file) every N decode steps "
                   "(0 = only at exit)")
@click.option("--prom_file", default=None, type=str,
              help="write Prometheus text exposition here (atomic "
                   "rewrite on the --metrics-every cadence and at exit; "
                   "node-exporter textfile-collector compatible)")
@click.option("--prom_port", default=0,
              help="serve Prometheus text exposition over HTTP on this "
                   "localhost port (0 = off)")
@click.option("--heartbeat", default=0.0,
              help="rewrite --prom_file at least every N seconds even "
                   "when idle (0 = only on the --metrics-every cadence). "
                   "The fleet collector reads exposition mtime as the "
                   "liveness signal; without a heartbeat an idle but "
                   "healthy replica looks dead")
@click.option("--journal_dir", default=None, type=str,
              help="journal accepted requests + emitted tokens to "
                   "DIR/journal.jsonl (crash-safe, append-only) so a "
                   "later --replay loses zero accepted work")
@click.option("--replay", "replay_dir", default=None, type=str,
              help="on startup, replay DIR/journal.jsonl: resume every "
                   "accepted-but-unfinished request bit-identically "
                   "(dedup on request id; finished work is settled, "
                   "never re-decoded)")
@click.option("--reload_watch", default=0.0, type=float,
              help="poll the checkpoint dir every N seconds and "
                   "hot-reload when a new complete checkpoint appears "
                   "(0 = off; SIGHUP always triggers a reload)")
@click.option("--reload_pin", "reload_pin_path", default=None, type=str,
              help="per-replica pin control file (reload.pin): when it "
                   "names a checkpoint, the --reload_watch poll loads "
                   "exactly that one (newest-wins suspended) and "
                   "answers through FILE.ack; at startup a pinned "
                   "checkpoint is restored directly. The deploy "
                   "controller's canary/promote seam. Implies "
                   "--reload_watch 2 when unset")
@click.option("--flight_dir", default=None, type=str,
              help="arm the flight recorder: keep the last "
                   "events/spans/requests in a bounded in-memory ring "
                   "and dump an atomic flight-<host>-<ts>.json here on "
                   "crash paths (chaos kill, stall escalation, "
                   "unhandled exception, second kill signal)")
@click.option("--profile_pin", "profile_pin_path", default=None, type=str,
              help="profile.pin control file: when it carries a token "
                   "(optionally '<token> <seconds>'), start a bounded "
                   "jax.profiler trace window on the live process and "
                   "answer through FILE.ack — no restart. Polled every "
                   "2s between decode steps")
@click.option("--profile_out", default=None, type=str,
              help="directory for on-demand profiler trace windows "
                   "(default: <profile_pin dir>/profiles)")
def main(checkpoint_path, max_slots, max_queue, max_len, quantize_int8,
         prefill_chunk, prefix_cache_mb, top_k, temperature, top_p, seed,
         socket_path, tcp_hostport, idle_timeout, metrics_every,
         prom_file, prom_port, heartbeat, journal_dir, replay_dir,
         reload_watch, reload_pin_path, flight_dir, profile_pin_path,
         profile_out):
    from progen_tpu import telemetry
    from progen_tpu.resilience.chaos import install_from_env
    from progen_tpu.telemetry import (
        prometheus_text,
        start_prometheus_server,
        write_prometheus,
    )
    from progen_tpu.tracking import make_tracker

    # serving chaos sites (serve/prefill_chunk, serve/decode, serve/reload*)
    # arm from the environment, same as cli/train.py — the serve
    # kill-matrix drives this process via PROGEN_CHAOS alone
    install_from_env()

    journal = None
    if journal_dir:
        from progen_tpu.serving import RequestJournal

        journal = RequestJournal(os.path.join(journal_dir, "journal.jsonl"))
    startup_pin = None
    if reload_pin_path:
        if not reload_watch:
            reload_watch = 2.0  # a pin nobody polls is a dead letter
        try:
            with open(reload_pin_path) as f:
                startup_pin = f.read().strip() or None
        except OSError:
            startup_pin = None
    # the one line that says what this replica is on (same record as
    # cli/train.py's; each of the router's --spawn children reports its
    # own visible device set here), before the restore touches the device
    from progen_tpu.profiling import announce_startup

    startup = announce_startup("serve")
    sched, engine, ckpt_name = _build(
        checkpoint_path, max_slots, max_len, max_queue,
        quantize_int8=quantize_int8, journal=journal,
        prefill_chunk=prefill_chunk, prefix_cache_mb=prefix_cache_mb,
        pin=startup_pin,
    )
    defaults = {
        "byte_codec": getattr(engine.model.config, "byte_codec", True),
        "length": engine.max_len, "top_k": top_k,
        "temperature": temperature, "top_p": top_p, "seed": seed,
    }
    tracker = make_tracker("progen-serve")
    # per-request async tracing: the scheduler's req/slots records and
    # the engine's serve/prefill_chunk spans land in the tracker's
    # events.jsonl — `progen-tpu-telemetry export-trace` renders each
    # accepted request as one async track (queued → prefill → decode)
    telemetry.configure(sink=tracker.log_event)
    telemetry.get_telemetry().emit(startup)
    run_dir = getattr(tracker, "path", None)
    if run_dir is not None:
        print(
            f"request traces: {run_dir}/events.jsonl "
            "(render with progen-tpu-telemetry export-trace)",
            file=sys.stderr,
        )

    # forensics: black-box ring + on-demand profiler window, both armed
    # only when asked — the flight-overhead bench pins the armed cost
    from progen_tpu.telemetry import flight as flight_mod

    if flight_dir:
        flight_mod.arm(flight_dir, metrics_fn=sched.metrics.snapshot)
        print(f"flight recorder armed: dumps to {flight_dir}",
              file=sys.stderr)
    prof_watcher = None
    if profile_pin_path:
        prof_out = profile_out or os.path.join(
            os.path.dirname(profile_pin_path) or ".", "profiles"
        )
        prof_watcher = flight_mod.ProfilePinWatcher(
            profile_pin_path, prof_out
        )
        print(f"profile pin watched: {profile_pin_path} "
              f"(windows to {prof_out})", file=sys.stderr)

    import time as _time

    hb = {"last": _time.monotonic()}

    from progen_tpu.checkpoint import checkpoint_digest, digest_gauge

    ckd = {"name": ckpt_name}

    def _digest_of(name):
        if not name:
            return -1.0
        return digest_gauge(checkpoint_digest(
            os.path.join(checkpoint_path, name)
        ))

    ckd["gauge"] = _digest_of(ckpt_name)

    def publish(step=None):
        # compile counts ride the metrics: the router's kill-matrix
        # reads the survivor's prom file to prove handoff didn't trigger
        # a recompile (resume state is shape-identical to fresh intake)
        sched.metrics.set_gauge(
            "prefill_compile_count", engine.prefill_compile_count()
        )
        sched.metrics.set_gauge(
            "decode_compile_count", engine.decode_compile_count()
        )
        # live checkpoint identity (first 48 digest bits as a float):
        # the deploy controller and the router read fleet skew from this
        sched.metrics.set_gauge("checkpoint_digest", ckd["gauge"])
        sched.metrics.log_to(tracker, step=step)
        if prom_file:
            write_prometheus(prom_file, prometheus_text(sched.metrics))
            hb["last"] = _time.monotonic()

    prom_srv = None
    if prom_port:
        prom_srv = start_prometheus_server(
            lambda: prometheus_text(sched.metrics), port=prom_port
        )
        print(
            f"prometheus on http://127.0.0.1:"
            f"{prom_srv.server_address[1]}/metrics",
            file=sys.stderr,
        )
    print(
        f"serving: max_slots={engine.max_slots} max_len={engine.max_len} "
        f"max_queue={sched.max_queue}"
        + (f" checkpoint={ckpt_name}" if ckpt_name else ""),
        file=sys.stderr,
    )

    # hot weight reload: SIGHUP (or the --reload_watch poller) stages
    # the newest verified checkpoint on a background thread; tick()
    # commits it between decode steps — zero recompiles, zero drops
    from progen_tpu.serving import WeightReloader

    reloader = WeightReloader(
        engine, checkpoint_path, metrics=sched.metrics,
        current=ckpt_name, pin_path=reload_pin_path,
    )
    # answer a pre-existing pin file now: committed when _build restored
    # it, rejected when it fell back — the controller must not wait on a
    # pin this process already settled
    reloader.note_startup_pin()
    reload_req = {"flag": False}

    def tick():
        """Once per serve-loop iteration, between decode steps."""
        # prom rewrite only (no tracker row): mtime freshness for the
        # fleet collector's staleness check, without metrics.jsonl spam
        if heartbeat and prom_file \
                and _time.monotonic() - hb["last"] >= heartbeat:
            write_prometheus(prom_file, prometheus_text(sched.metrics))
            hb["last"] = _time.monotonic()
        if reload_req["flag"]:
            reload_req["flag"] = False
            if reloader.request_reload():
                print("reload: loading newest checkpoint in background",
                      file=sys.stderr)
        if reload_watch:
            reloader.poll_watch(reload_watch)
        if prof_watcher is not None:
            prof_watcher.poll_watch()
        name = reloader.maybe_commit()
        if name is not None:
            ckd["name"], ckd["gauge"] = name, _digest_of(name)
            print(f"reload: now serving {name}", file=sys.stderr)
            publish()  # the digest gauge must not wait a metrics cadence
        elif reloader.last_error is not None:
            print(f"reload: rejected ({reloader.last_error}) — still "
                  f"serving {reloader.current}", file=sys.stderr)
            reloader.last_error = None

    # crash recovery: resume the previous process's unfinished accepted
    # requests before opening intake. Requests whose journaled stream
    # already hit its stop rule are settled here (done event, no decode)
    replayed_lines = []
    starts0 = {}
    if replay_dir:
        from progen_tpu.serving import replay_into

        jpath = os.path.join(replay_dir, "journal.jsonl")
        if os.path.exists(jpath):
            summary = replay_into(sched, jpath)
            for req in summary["resumed"]:
                starts0[req.id] = len(req.prime) + (1 if req.add_bos else 0)
            for f in summary["finished"]:
                replayed_lines.append(json.dumps({
                    "event": "done", "id": f["id"],
                    **_as_text(f["emitted"], defaults),
                    "n_generated": 0, "ttft_s": 0.0, "latency_s": 0.0,
                    "replayed": True,
                }))
            print(
                f"replay: resumed {len(summary['resumed'])} request(s), "
                f"settled {len(summary['finished'])} already-finished, "
                f"skipped {summary['skipped_done']} done "
                f"({summary['dropped_lines']} torn journal line(s))",
                file=sys.stderr,
            )
        else:
            print(f"replay: no journal at {jpath}", file=sys.stderr)

    # graceful drain: the FIRST SIGTERM/SIGINT closes intake — queued
    # requests are shed as 'rejected: draining', in-flight slots decode
    # to completion, metrics flush, exit 0 (what a rolling restart
    # wants). A SECOND signal means "now": close the open per-request
    # trace tracks (reason 'killed' — the post-mortem trace must be
    # honest about what was in flight) and exit immediately.
    import signal

    shutdown = {"flag": False}

    def _request_drain(signum, frame):
        if shutdown["flag"]:
            print(f"signal {signum} again: exiting now", file=sys.stderr)
            try:
                sched.close_tracks("killed")
            except Exception:
                pass  # a torn trace line beats a hung exit
            # last act: the black box (atomic — a kill mid-dump leaves
            # no torn file, and dump_now never raises)
            flight_mod.dump_now("killed", note=f"signal {signum}")
            sys.stderr.flush()
            os._exit(1)
        shutdown["flag"] = True
        print(
            f"signal {signum}: draining — intake closed, finishing "
            "in-flight requests; signal again to kill",
            file=sys.stderr,
        )

    def _request_reload(signum, frame):
        reload_req["flag"] = True  # handler-minimal; tick() does the work

    old_term = signal.signal(signal.SIGTERM, _request_drain)
    old_int = signal.signal(signal.SIGINT, _request_drain)
    old_hup = signal.signal(signal.SIGHUP, _request_reload)
    try:
        if tcp_hostport:
            _serve_tcp(sched, defaults, tcp_hostport, publish,
                       metrics_every, shutdown, tick=tick,
                       idle_timeout=idle_timeout)
        elif socket_path:
            _serve_socket(sched, defaults, socket_path, publish,
                          metrics_every, shutdown, tick=tick)
        else:
            _serve_stdio(sched, defaults, publish, metrics_every,
                         shutdown, tick=tick, starts0=starts0,
                         preamble=replayed_lines)
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGHUP, old_hup)
        publish()
        print(
            f"compile counts: prefill={engine.prefill_compile_count()} "
            f"decode={engine.decode_compile_count()}",
            file=sys.stderr,
        )
        if prom_srv is not None:
            prom_srv.shutdown()
        if prof_watcher is not None:
            prof_watcher.close()  # flush an in-flight profiler window
        flight_mod.disarm()
        telemetry.configure()  # detach before the sink closes
        tracker.finish()
        if journal is not None:
            journal.close()


def _submit_line(sched, line, defaults):
    """Parse + submit one request line; returns (rejection_line | None,
    request | None)."""
    req, err = _parse_request(line, defaults)
    if err is not None:
        rid = req.id if req is not None else None
        return json.dumps(
            {"event": "rejected", "id": rid, "reason": err}
        ), None
    ok, reason = sched.submit(req)
    if not ok:
        return json.dumps(
            {"event": "rejected", "id": req.id, "reason": reason}
        ), None
    return None, req


def _shed_lines(sched, starts, owners=None):
    """Requests the scheduler shed (deadline expiry, drain) become
    rejection events for their owners; returns (fd_or_None, line)
    pairs — fd is None on the stdio transport."""
    out = []
    for req, reason in sched.pop_expired():
        starts.pop(req.id, None)
        if owners is None:
            out.append((None, json.dumps({
                "event": "rejected", "id": req.id, "reason": reason,
            })))
        else:
            fd, public = owners.pop(req.id, (None, None))
            if fd is not None:
                out.append((fd, json.dumps({
                    "event": "rejected", "id": public, "reason": reason,
                })))
    return out


def _serve_stdio(sched, defaults, publish, metrics_every, shutdown,
                 tick=None, starts0=None, preamble=None):
    """stdin-JSONL transport: poll stdin between decode steps so new
    requests join mid-flight (continuous batching, not read-all-then-
    drain); EOF stops intake and the loop drains what remains. A drain
    signal (see main) also stops intake, but sheds the QUEUE — only
    in-flight slots run to completion. ``tick`` runs once per loop
    iteration (reload staging/commit); ``starts0``/``preamble`` carry
    replayed-request state from --replay."""
    starts = dict(starts0 or {})
    out = sys.stdout
    eof = False
    drained = False
    steps = 0
    buf = ""  # bytes off the pipe that don't yet end in a newline

    def emit(lines):
        for ln in lines:
            out.write(ln + "\n")
        out.flush()

    emit(list(preamble or []))
    while (not eof and not shutdown["flag"]) or sched.has_work:
        if tick is not None:
            tick()
        if shutdown["flag"] and not drained:
            drained = True
            sched.drain_queue()
        # take every line already waiting; bounded idle wait (not a full
        # block) so a drain signal interrupts within one tick. Reads the
        # raw fd into an explicit line buffer: select()+readline() loses
        # lines — readline pulls everything waiting on the pipe into the
        # TextIOWrapper buffer, returns ONE line, and select never
        # reports the rest (they're no longer on the fd), so a client
        # that writes a batch of requests and keeps the pipe open would
        # see all but the first stall until its next write or EOF.
        while not eof and not shutdown["flag"]:
            nl = buf.find("\n")
            if nl < 0:
                timeout = 0.2 if not sched.has_work else 0.0
                try:
                    ready, _, _ = select.select([sys.stdin], [], [], timeout)
                except OSError:
                    break
                if not ready:
                    break
                data = os.read(sys.stdin.fileno(), 65536)
                if not data:
                    eof = True
                    # a final unterminated line still gets an answer (a
                    # torn write parses as a rejection, not silence)
                    line, buf = buf, ""
                else:
                    buf += data.decode("utf-8", errors="replace")
                    continue
            else:
                line, buf = buf[:nl], buf[nl + 1:]
            if not line.strip():
                continue
            rej, req = _submit_line(sched, line, defaults)
            if rej is not None:
                emit([rej])
            else:
                starts[req.id] = len(req.prime) + (1 if req.add_bos else 0)
        if sched.has_work:
            events, comps = sched.step()
            emit(_events_to_lines(events, comps, starts, defaults))
            steps += 1
            if metrics_every and steps % metrics_every == 0:
                publish(steps)
        # requests shed this tick (deadline expiry inside step(), or the
        # drain above) surface as rejection events
        emit([ln for _, ln in _shed_lines(sched, starts)])


def _handle_client_line(sched, line, defaults, fd, owners, starts, send):
    """One client line on a connection-oriented transport: a release
    ctl (the router's rebalance/scale-down path asking this replica to
    surrender a queued request) or a request submission. Request ids
    are namespaced per connection so two clients may both call their
    request "1"."""
    try:
        ctl = json.loads(line)
    except ValueError:
        ctl = None
    if isinstance(ctl, dict) and ctl.get("ctl") == "release":
        public = str(ctl.get("id"))
        internal = f"{fd}:{public}"
        released = sched.release(internal)
        if released:
            owners.pop(internal, None)
            starts.pop(internal, None)
        send(fd, [json.dumps({
            "event": "released", "id": public, "released": released,
        })])
        return
    req, err = _parse_request(line, defaults)
    if req is not None and err is None:
        public = req.id
        req.id = f"{fd}:{public}"
        ok, reason = sched.submit(req)
        if ok:
            owners[req.id] = (fd, public)
            starts[req.id] = len(req.prime) + (1 if req.add_bos else 0)
            return
        err = reason
        public_id = public
    else:
        public_id = req.id if req is not None else None
    send(fd, [json.dumps({
        "event": "rejected", "id": public_id, "reason": err,
    })])


def _serve_socket(sched, defaults, socket_path, publish, metrics_every,
                  shutdown, tick=None):
    """Unix-socket transport: one select loop over {listener, clients,
    engine}; request ids are namespaced per connection internally so two
    clients may both call their request "1". On drain the listener
    closes (new connections refused), the queue is shed, in-flight
    slots finish streaming to their clients, then the loop exits."""
    if os.path.exists(socket_path):
        os.unlink(socket_path)
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(socket_path)
    srv.listen(16)
    srv.setblocking(False)
    clients = {}  # fd -> (sock, recv_buffer)
    owners = {}  # internal request id -> fd
    starts = {}
    steps = 0
    print(f"listening on {socket_path}", file=sys.stderr)

    def send(fd, internal_lines):
        sock, _ = clients.get(fd, (None, None))
        if sock is None:
            return
        try:
            for ln in internal_lines:
                sock.sendall(ln.encode() + b"\n")
        except OSError:
            _drop(fd)

    def _drop(fd):
        sock, _ = clients.pop(fd, (None, None))
        if sock is not None:
            sock.close()

    drained = False
    try:
        while True:
            if tick is not None:
                tick()
            if shutdown["flag"]:
                if not drained:
                    drained = True
                    srv.close()  # refuse new connections during drain
                    sched.drain_queue()
                    for fd, ln in _shed_lines(sched, starts, owners):
                        send(fd, [ln])
                if not sched.has_work:
                    break
            rlist = ([] if drained else [srv]) + [
                s for s, _ in clients.values()
            ]
            timeout = 0.0 if sched.has_work else 0.2
            try:
                ready, _, _ = (
                    select.select(rlist, [], [], timeout)
                    if rlist else ([], [], [])
                )
            except OSError:
                continue  # a peer vanished between list and select
            for sock in ready:
                if sock is srv:
                    conn, _ = srv.accept()
                    conn.setblocking(False)
                    clients[conn.fileno()] = (conn, b"")
                    continue
                fd = sock.fileno()
                try:
                    data = sock.recv(65536)
                except OSError:
                    data = b""
                if not data:
                    _drop(fd)
                    continue
                _, buf = clients[fd]
                buf += data
                *lines, buf = buf.split(b"\n")
                clients[fd] = (sock, buf)
                for raw in lines:
                    if not raw.strip():
                        continue
                    _handle_client_line(
                        sched, raw.decode("utf-8", "replace"), defaults,
                        fd, owners, starts, send,
                    )
            if sched.has_work:
                events, comps = sched.step()
                for fd, ln in _shed_lines(sched, starts, owners):
                    send(fd, [ln])
                for ev in events:
                    fd, public = owners.get(ev.request_id, (None, None))
                    if fd is None:
                        continue
                    ev.request_id = public
                    send(fd, _events_to_lines([ev], [], starts, defaults))
                for c in comps:
                    fd, public = owners.pop(c.request_id, (None, None))
                    if fd is None:
                        continue
                    start = starts.pop(c.request_id, 0)
                    c.request_id = public
                    send(fd, _events_to_lines(
                        [], [c], {public: start}, defaults))
                steps += 1
                if metrics_every and steps % metrics_every == 0:
                    publish(steps)
    finally:
        for fd in list(clients):
            _drop(fd)
        srv.close()
        if os.path.exists(socket_path):
            os.unlink(socket_path)


def _serve_tcp(sched, defaults, hostport, publish, metrics_every,
               shutdown, tick=None, idle_timeout=0.0):
    """Framed-TCP transport: the unix-socket loop with frames instead
    of newlines (fleet/transport.py owns validation, drop records and
    condemnation — a framing violation reads as EOF here). Same id
    namespacing, same drain contract; additionally reaps peers silent
    past ``idle_timeout``."""
    from progen_tpu.fleet.transport import FramedListener, parse_hostport

    host, port = parse_hostport(hostport)
    listener = FramedListener(host, port, idle_timeout=idle_timeout)
    clients = {}  # fd -> FramedConnection
    owners = {}  # internal request id -> (fd, public id)
    starts = {}
    steps = 0
    # the bound port line is the startup handshake: with PORT 0 it is
    # the only place the ephemeral port exists
    print(f"listening on tcp {listener.host}:{listener.port}",
          file=sys.stderr)
    sys.stderr.flush()

    def send(fd, internal_lines):
        conn = clients.get(fd)
        if conn is None:
            return
        try:
            for ln in internal_lines:
                conn.send_line(ln)
        except OSError:
            _drop(fd)

    def _drop(fd):
        conn = clients.pop(fd, None)
        if conn is not None:
            conn.close()

    drained = False
    try:
        while True:
            if tick is not None:
                tick()
            for fd, conn in list(clients.items()):
                if conn.idle_expired():
                    _drop(fd)
            if shutdown["flag"]:
                if not drained:
                    drained = True
                    listener.close()  # refuse new dials during drain
                    sched.drain_queue()
                    for fd, ln in _shed_lines(sched, starts, owners):
                        send(fd, [ln])
                if not sched.has_work:
                    break
            rlist = ([] if drained else [listener]) + list(clients.values())
            timeout = 0.0 if sched.has_work else 0.2
            try:
                ready, _, _ = (
                    select.select(rlist, [], [], timeout)
                    if rlist else ([], [], [])
                )
            except OSError:
                continue  # a peer vanished between list and select
            for obj in ready:
                if obj is listener:
                    conn = listener.accept()
                    if conn is not None:
                        clients[conn.fileno()] = conn
                    continue
                if obj.sock is None:
                    continue  # dropped earlier this iteration
                fd = obj.fileno()
                lines, eof = obj.recv_lines()
                for line in lines:
                    if not line.strip():
                        continue
                    _handle_client_line(sched, line, defaults, fd,
                                        owners, starts, send)
                if eof:
                    _drop(fd)
            if sched.has_work:
                events, comps = sched.step()
                for fd, ln in _shed_lines(sched, starts, owners):
                    send(fd, [ln])
                for ev in events:
                    fd, public = owners.get(ev.request_id, (None, None))
                    if fd is None:
                        continue
                    ev.request_id = public
                    send(fd, _events_to_lines([ev], [], starts, defaults))
                for c in comps:
                    fd, public = owners.pop(c.request_id, (None, None))
                    if fd is None:
                        continue
                    start = starts.pop(c.request_id, 0)
                    c.request_id = public
                    send(fd, _events_to_lines(
                        [], [c], {public: start}, defaults))
                steps += 1
                if metrics_every and steps % metrics_every == 0:
                    publish(steps)
    finally:
        for fd in list(clients):
            _drop(fd)
        listener.close()


if __name__ == "__main__":
    main()

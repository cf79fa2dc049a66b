"""Serve kill-matrix: SIGKILL a real serving process at injected points
(PROGEN_CHAOS serving targets), restart with ``--replay``, and assert
the zero-downtime invariants end to end:

  1. every request the dead process ACCEPTED (journal ``accept``) is
     settled exactly once across the two lives — no lost work, no
     double-answers;
  2. no (request, index) token is ever emitted twice — the journal's
     write-before-emit ordering survives a kill at any decode step;
  3. a SIGHUP hot-reload under live traffic commits the new checkpoint
     with zero rejected/dropped requests;
  4. (``slow``) the resumed streams are bit-identical to ``sample_fast``
     on the journaled keys — crash+replay is invisible in the tokens.

These run REAL ``python -m progen_tpu.cli.serve`` subprocesses (a
SIGKILL rule in-process would take pytest down with it). One kill case
and the SIGHUP reload run in tier-1; the prefill/reload kills and the
randomized parity sweep are ``slow``.
"""

import json
import os
import select
import signal
import subprocess
import sys
import time
import warnings
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

# num_tokens=256 so the byte tokenizer's ids are all servable
KILL_CFG = dict(
    num_tokens=256, dim=32, seq_len=32, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2, dtype="float32",
)


@pytest.fixture(scope="module")
def workspace(tmp_path_factory):
    """A checkpoint store with one saved checkpoint plus the live
    (model, params) so slow tests can compute sample_fast references."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen

    root = tmp_path_factory.mktemp("serve_kill")
    config = ProGenConfig(**KILL_CFG)
    model = ProGen(config)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, config.seq_len), jnp.int32)
    )
    params = meta.unbox(variables)["params"]
    _, _, save = get_checkpoint_fns(str(root / "ck"))
    save(Package(0, {"params": params}, config.to_dict(), "kill-matrix"))
    return {
        "root": root, "ck": root / "ck",
        "model": model, "params": params, "config": config,
    }


def _spawn(ck, journal_dir, *, chaos="", replay=False, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PROGEN_CHAOS"] = chaos
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    args = [
        sys.executable, "-m", "progen_tpu.cli.serve",
        "--checkpoint_path", str(ck),
        "--max-slots", "2", "--max-queue", "16", "--max-len", "24",
        "--journal_dir", str(journal_dir),
    ]
    args += list(extra)
    if replay:
        args += ["--replay", str(journal_dir)]
    return subprocess.Popen(
        args, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True, bufsize=1,
    )


def _requests(n, length=16):
    return [
        json.dumps({
            "id": f"r{i}", "prime": "MKV", "length": length,
            "seed": 70 + i,
        })
        for i in range(n)
    ]


def _parse_events(out: str):
    """Protocol lines -> (tokens: [(id, index, token)], done_ids: list).
    A SIGKILLed writer may tear the final line — skip unparsable."""
    tokens, done = [], []
    for line in out.splitlines():
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if ev.get("event") == "token":
            tokens.append((ev["id"], ev["index"], ev["token"]))
        elif ev.get("event") == "done":
            done.append(ev["id"])
    return tokens, done


def _journal_accepts(journal_dir):
    """request id -> FIRST accept record (the original submission —
    re-accepts from a replayed run carry an advanced key)."""
    from progen_tpu.telemetry.trace import iter_jsonl

    accepts = {}
    path = Path(journal_dir) / "journal.jsonl"
    if not path.exists():  # polled before the serve process opened it
        return accepts
    for rec in iter_jsonl(path):
        if rec.get("ev") == "journal" and rec.get("op") == "accept":
            accepts.setdefault(rec["req"], rec)
    return accepts


def _kill_then_replay(workspace, tmp_path, chaos, n_requests=4,
                      requests=None, extra=()):
    """Shared body: run serve under a kill rule, then a chaos-free
    ``--replay`` run (same flags); return
    (tokens1, done1, tokens2, done2, accepts)."""
    jd = tmp_path / "jd"
    proc = _spawn(workspace["ck"], jd, chaos=chaos, extra=extra)
    reqs = _requests(n_requests) if requests is None else requests
    out1, err1 = proc.communicate(
        input="\n".join(reqs) + "\n", timeout=240
    )
    assert proc.returncode == -9, (out1[-1000:], err1[-2000:])

    proc = _spawn(workspace["ck"], jd, replay=True, extra=extra)
    out2, err2 = proc.communicate(input="", timeout=240)
    assert proc.returncode == 0, (out2[-1000:], err2[-2000:])
    assert "replay:" in err2

    tokens1, done1 = _parse_events(out1)
    tokens2, done2 = _parse_events(out2)
    accepts = _journal_accepts(jd)
    assert accepts, "the dead process accepted nothing — kill came too early"

    # invariant 1: every accepted request settled exactly once overall
    all_done = done1 + done2
    assert sorted(all_done) == sorted(accepts), (done1, done2)
    # invariant 2: no (request, index) emitted twice across the lives
    pairs = [(i, ix) for i, ix, _ in tokens1 + tokens2]
    assert len(set(pairs)) == len(pairs)
    return tokens1, done1, tokens2, done2, accepts


def _assert_parity(workspace, accepts, tokens):
    """Every emitted (id, index, token) — from either life — must match
    the uninterrupted sample_fast stream for the journaled key."""
    import jax.numpy as jnp
    import numpy as np

    from progen_tpu.sampling import sample_fast

    for rid, acc in accepts.items():
        ref = np.asarray(sample_fast(
            jnp.asarray(acc["key"], jnp.uint32),
            workspace["model"], workspace["params"],
            jnp.asarray(acc["prime"], jnp.int32), acc["length"],
            top_k=acc["top_k"], add_bos=acc["add_bos"],
            temperature=acc["temperature"], top_p=acc["top_p"],
        ))
        for i, ix, tok in tokens:
            if i == rid:
                assert ref[ix] == tok, (rid, ix, tok, int(ref[ix]))


class TestDeterministicKills:
    def test_kill_mid_decode_replay_recovers_all(
        self, workspace, tmp_path
    ):
        """Die at the 6th decode step with four requests in flight; the
        replay run must settle every accepted request with zero
        duplicate tokens."""
        tokens1, done1, tokens2, _, _ = _kill_then_replay(
            workspace, tmp_path, "serve/decode:kill@6"
        )
        assert tokens1, "kill@6 should land after some tokens streamed"
        # the kill landed mid-flight: someone was still decoding
        assert tokens2, "nothing resumed — kill came after all work done"

    def test_kill_mid_chunk_replay_settles_once(
        self, workspace, tmp_path
    ):
        """SIGKILL inside the second prefill CHUNK — the slot is
        acquired and partially primed but never activated. The journal
        must hold no partial-prefill state (ops stay accept/token/done
        only), and a chaos-free ``--replay`` with the same chunked
        flags must settle every accepted request exactly once: the
        whole prefill simply re-runs from the accept record."""
        reqs = [
            json.dumps({
                "id": f"c{i}", "prime": "MKVLATGLLSDQ", "length": 20,
                "seed": 50 + i,
            })
            for i in range(4)
        ]
        jd_ops = []
        _, _, _, done2, accepts = _kill_then_replay(
            workspace, tmp_path, "serve/prefill_chunk:kill@2",
            requests=reqs,
            extra=["--prefill_chunk", "4", "--prefix_cache_mb", "8"],
        )
        assert done2, "replay settled nothing"
        # zero partial-prefill journal records: the replay alphabet is
        # still accept/token/done — chunk progress is never journaled
        from progen_tpu.telemetry.trace import iter_jsonl

        for rec in iter_jsonl(tmp_path / "jd" / "journal.jsonl"):
            if rec.get("ev") == "journal":
                jd_ops.append(rec["op"])
        assert jd_ops and set(jd_ops) <= {"accept", "token", "done"}


@pytest.mark.slow
class TestKillMatrixSlow:
    def test_kill_mid_prefill_replay_recovers_all(
        self, workspace, tmp_path
    ):
        """Die inside the second request's prefill: accepted-but-never-
        admitted requests must replay too."""
        _, _, tokens2, done2, _ = _kill_then_replay(
            workspace, tmp_path, "serve/prefill_chunk:kill@2"
        )
        assert done2, "replay settled nothing"

    def test_kill_mid_reload_never_torn(self, workspace, tmp_path):
        """SIGKILL inside the reload span (background load): the store
        and journal stay consistent — a restart replays every accepted
        request and serves from the intact checkpoint."""
        jd = tmp_path / "jd"
        proc = _spawn(workspace["ck"], jd, chaos="serve/reload:kill@1")
        proc.stdin.write("\n".join(_requests(4, length=24)) + "\n")
        proc.stdin.flush()
        # wait for acceptance (journal accept records) before the SIGHUP
        # so the kill provably strands accepted work
        deadline = time.time() + 180
        while time.time() < deadline:
            if len(_journal_accepts(jd)) == 4:
                break
            if proc.poll() is not None:
                pytest.fail(f"serve died early: {proc.stderr.read()[-2000:]}")
            time.sleep(0.5)
        assert len(_journal_accepts(jd)) == 4
        os.kill(proc.pid, signal.SIGHUP)
        out1, err1 = proc.communicate(timeout=240)
        assert proc.returncode == -9, (out1[-1000:], err1[-2000:])

        proc = _spawn(workspace["ck"], jd, replay=True)
        out2, err2 = proc.communicate(input="", timeout=240)
        assert proc.returncode == 0, (out2[-1000:], err2[-2000:])
        _, done1 = _parse_events(out1)
        _, done2 = _parse_events(out2)
        assert sorted(done1 + done2) == sorted(_journal_accepts(jd))

    @pytest.mark.parametrize("n", [3, 9, 14])
    def test_randomized_decode_kill_bit_parity(
        self, workspace, tmp_path, n
    ):
        """Sweep the kill point across the decode timeline; the union of
        pre- and post-crash tokens must be bit-identical to the
        uninterrupted reference stream."""
        tokens1, _, tokens2, _, accepts = _kill_then_replay(
            workspace, tmp_path, f"serve/decode:kill@{n}"
        )
        _assert_parity(workspace, accepts, tokens1 + tokens2)


class TestSighupReload:
    def test_sighup_reload_under_live_traffic(self, workspace, tmp_path):
        """Serve traffic, save a new checkpoint, SIGHUP, serve more
        traffic: the reload commits ('now serving'), and every request
        from both waves completes with zero rejections."""
        import jax

        from progen_tpu.checkpoint import Package, get_checkpoint_fns

        jd = tmp_path / "jd"
        proc = _spawn(workspace["ck"], jd)
        out_lines, err_lines = [], []
        wave1 = _requests(2, length=20)
        proc.stdin.write("\n".join(wave1) + "\n")
        proc.stdin.flush()
        # wait for first tokens so the engine is provably serving
        assert _pump(
            proc, out_lines, err_lines,
            lambda: any('"token"' in ln for ln in out_lines), 180,
        ), "no tokens before the reload"

        _, _, save = get_checkpoint_fns(str(workspace["ck"]))
        params_b = jax.tree.map(lambda x: x * 1.3, workspace["params"])
        saved = save(Package(
            1, {"params": params_b}, workspace["config"].to_dict(), "b",
        ))
        os.kill(proc.pid, signal.SIGHUP)
        wave2 = [
            json.dumps({"id": f"w{i}", "prime": "GA", "length": 16,
                        "seed": 90 + i})
            for i in range(2)
        ]
        proc.stdin.write("\n".join(wave2) + "\n")
        proc.stdin.flush()
        # stdin stays open (the loop keeps ticking) until the background
        # load stages and the serve loop commits it between steps
        committed = f"now serving {Path(saved).name}"
        assert _pump(
            proc, out_lines, err_lines,
            lambda: any(committed in ln for ln in err_lines), 180,
        ), "\n".join(err_lines)[-2000:]
        proc.stdin.close()  # EOF -> graceful drain
        assert _pump(  # read both pipes to exhaustion
            proc, out_lines, err_lines,
            lambda: all(t[2] for t in proc._pump_tails.values()), 240,
        ), "serve did not drain after EOF"
        proc.wait(timeout=60)
        all_out = "\n".join(out_lines)
        err = "\n".join(err_lines)
        assert proc.returncode == 0, err[-2000:]
        _, done = _parse_events(all_out)
        assert sorted(done) == ["r0", "r1", "w0", "w1"]  # zero dropped
        assert '"rejected"' not in all_out
        assert "rejected" not in err


def _pump(proc, out_lines, err_lines, pred, timeout_s):
    """Drain both pipes into line lists until ``pred()`` or deadline.

    Reads the raw fds — never ``proc.stdout.readline()`` — because mixing
    buffered reads with a later ``communicate()``/raw drain strands
    complete lines inside the TextIOWrapper and silently drops events."""
    tails = getattr(proc, "_pump_tails", None)
    if tails is None:
        # fd -> [partial line, destination list, saw EOF]
        tails = proc._pump_tails = {
            proc.stdout.fileno(): ["", out_lines, False],
            proc.stderr.fileno(): ["", err_lines, False],
        }
    deadline = time.time() + timeout_s
    while not pred():
        if time.time() > deadline:
            return False
        live = [fd for fd, t in tails.items() if not t[2]]
        if not live:
            return pred()
        r, _, _ = select.select(live, [], [], 0.5)
        for fd in r:
            data = os.read(fd, 65536)
            t = tails[fd]
            if not data:
                t[2] = True
                if t[0]:
                    t[1].append(t[0])
                    t[0] = ""
                continue
            text = t[0] + data.decode("utf-8", "replace")
            *full, t[0] = text.split("\n")
            t[1].extend(full)
        if proc.poll() is not None and not r:
            return pred()
    return True


class TestChaosTargets:
    def test_unknown_target_warns_once(self):
        """A rule aimed at a nonexistent site never fires; installing it
        must say so — once per target per process."""
        from progen_tpu.resilience import chaos

        chaos._WARNED_UNKNOWN.discard("bogus/site")
        try:
            with pytest.warns(UserWarning, match="bogus/site"):
                # deliberately-unknown target: the warn-once under test
                chaos.install("bogus/site:fail@99")  # progen: ignore[PGL009]
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                # second install: silent (warn-once)
                chaos.install("bogus/site:fail@99")  # progen: ignore[PGL009]
        finally:
            chaos.uninstall()

    def test_serving_targets_are_known(self):
        from progen_tpu.resilience import chaos

        for target in ("serve/prefill", "serve/prefill_chunk",
                       "serve/decode", "serve/reload",
                       "serve/reload_commit"):
            assert target in chaos.KNOWN_TARGETS
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            chaos.install("serve/decode:kill@999")
        chaos.uninstall()

"""Fleet kill-matrix: SIGKILL replicas behind the router at injected
points (PROGEN_CHAOS) and assert the elastic-serving invariants across
the whole fleet:

  1. every request the fleet ACCEPTED settles exactly once — a replica
     death mid-stream hands its journal-accepted work to a survivor,
     nothing is lost, nothing answered twice;
  2. no (request, index) token is ever emitted twice across replicas —
     journal write-before-emit plus the router's gap-fill dedup;
  3. resumed streams are bit-identical to the uninterrupted
     ``sample_fast`` reference on the ORIGINAL journaled key;
  4. the surviving replica's ``decode_compile_count`` stays at 1 —
     handed-off resume state is shape-identical to fresh intake;
  5. a restart of the dead replica with ``--replay`` resumes ZERO
     requests — the router's ``handed_off`` ownership marks make
     double-serving impossible;
  6. transient faults at the router's own chaos sites
     (``router/dispatch``, ``router/handoff``) are absorbed, not
     amplified into lost requests.

These run REAL subprocesses: N ``cli/serve --socket`` replicas plus one
``cli/router`` front (a SIGKILL rule in-process would take pytest down
with it). The same invariants hold per transport: one fleet case runs
the victim over framed TCP (``--tcp`` / ``--replica tcp=``,
progen_tpu/fleet/transport.py) to lock the wire-format claim that a
SIGKILL mid-TCP-stream settles exactly once via ``--replay`` with
bit-parity. One mid-decode replica kill per transport runs in tier-1;
the prefill kill, router-site faults, and the parity sweep are
``slow``.
"""

import json
import os
import re
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

# journal ids namespace twice on the way down: the replica's socket
# transport prepends "{fd}:", the router's wire ids prepend "q{seq}-"
_NS_RE = re.compile(r"^(?:\d+:)?(?:q\d+-)?")


def _public_id(journal_id: str) -> str:
    return _NS_RE.sub("", journal_id)


@pytest.fixture(scope="module")
def workspace(tmp_path_factory):
    """A checkpoint store with one saved checkpoint plus the live
    (model, params) so parity tests can compute sample_fast references."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen

    root = tmp_path_factory.mktemp("router_kill")
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


def _env(chaos=""):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PROGEN_CHAOS"] = chaos
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    return env


def _spawn_replica(ck, rdir, *, chaos="", replay=False):
    rdir = Path(rdir)
    rdir.mkdir(parents=True, exist_ok=True)
    args = [
        sys.executable, "-m", "progen_tpu.cli.serve",
        "--checkpoint_path", str(ck),
        "--max-slots", "2", "--max-queue", "16", "--max-len", "24",
        "--socket", str(rdir / "serve.sock"),
        "--journal_dir", str(rdir),
        "--prom_file", str(rdir / "metrics.prom"),
        "--metrics-every", "2",
    ]
    if replay:
        args += ["--replay", str(rdir)]
    return subprocess.Popen(
        args, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=_env(chaos), text=True, bufsize=1,
    )


def _spawn_router(rdirs, *, chaos=""):
    specs = []
    for rdir in rdirs:
        rdir = Path(rdir)
        specs.append(
            f"sock={rdir / 'serve.sock'},journal={rdir},"
            f"prom={rdir / 'metrics.prom'}"
        )
    return _spawn_router_specs(specs, chaos=chaos)


def _spawn_router_specs(specs, *, chaos=""):
    args = [sys.executable, "-m", "progen_tpu.cli.router"]
    for spec in specs:
        args += ["--replica", spec]
    return subprocess.Popen(
        args, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=_env(chaos), text=True, bufsize=1,
    )


def _spawn_replica_tcp(ck, rdir, *, chaos="", replay=False):
    """A replica serving framed TCP on an ephemeral loopback port;
    stderr goes to ``rdir/stderr.log`` so the bound port (and later the
    replay report) can be read without racing a pipe."""
    rdir = Path(rdir)
    rdir.mkdir(parents=True, exist_ok=True)
    args = [
        sys.executable, "-m", "progen_tpu.cli.serve",
        "--checkpoint_path", str(ck),
        "--max-slots", "2", "--max-queue", "16", "--max-len", "24",
        "--tcp", "127.0.0.1:0",
        "--journal_dir", str(rdir),
        "--prom_file", str(rdir / "metrics.prom"),
        "--metrics-every", "2",
    ]
    if replay:
        args += ["--replay", str(rdir)]
    return subprocess.Popen(
        args, stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
        stderr=open(rdir / "stderr.log", "a"), env=_env(chaos),
    )


def _wait_tcp_port(proc, rdir, timeout_s=240, min_count=1):
    """Block until the TCP replica prints its bound ephemeral port;
    returns the ``host:port`` string. A replay rebirth appends a fresh
    line to the same log, so its caller passes ``min_count=2`` — the
    dead first life's line must not read as the new process being up."""
    log = Path(rdir) / "stderr.log"
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        text = log.read_text() if log.exists() else ""
        ports = re.findall(r"listening on tcp (\S+)", text)
        if len(ports) >= min_count:
            return ports[-1]
        if proc.poll() is not None:
            pytest.fail(
                f"tcp replica died during startup: {text[-2000:]}"
            )
        time.sleep(0.25)
    pytest.fail("tcp replica never printed its port")


def _wait_sockets(procs_dirs, timeout_s=240):
    """Block until every replica has bound its socket (JAX import +
    checkpoint load dominate startup)."""
    deadline = time.time() + timeout_s
    for proc, rdir in procs_dirs:
        sock = Path(rdir) / "serve.sock"
        while not sock.exists():
            if proc.poll() is not None:
                pytest.fail(
                    f"replica died during startup: "
                    f"{proc.stderr.read()[-2000:]}"
                )
            if time.time() > deadline:
                pytest.fail(f"replica never bound {sock}")
            time.sleep(0.25)


def _requests(n, length=16):
    return [
        json.dumps({
            "id": f"r{i}", "prime": "MKV", "length": length,
            "seed": 70 + i,
        })
        for i in range(n)
    ]


def _parse_events(lines):
    """Protocol lines -> (tokens, done_ids, rejected). A killed writer
    may tear a line — skip unparsable."""
    tokens, done, rejected = [], [], []
    for line in lines:
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if ev.get("event") == "token":
            tokens.append((ev["id"], ev["index"], ev["token"]))
        elif ev.get("event") == "done":
            done.append(ev["id"])
        elif ev.get("event") == "rejected":
            rejected.append(ev)
    return tokens, done, rejected


def _journal_accepts(journal_dir):
    """journal id -> FIRST accept record in this journal."""
    from progen_tpu.telemetry.trace import iter_jsonl

    accepts = {}
    path = Path(journal_dir) / "journal.jsonl"
    if not path.exists():
        return accepts
    for rec in iter_jsonl(path):
        if rec.get("ev") == "journal" and rec.get("op") == "accept":
            accepts.setdefault(rec["req"], rec)
    return accepts


def _original_accepts(rdirs):
    """public id -> the ORIGINAL accept across the fleet's journals (a
    handoff re-accept carries a compound prime, so the original is the
    one with the shortest prime)."""
    out = {}
    for rdir in rdirs:
        for jid, acc in _journal_accepts(rdir).items():
            pub = _public_id(jid)
            if pub not in out or len(acc["prime"]) < len(out[pub]["prime"]):
                out[pub] = acc
    return out


def _assert_parity(workspace, originals, tokens):
    """Every (id, index, token) emitted by the FLEET must match the
    uninterrupted sample_fast stream of the original journaled key."""
    import jax.numpy as jnp
    import numpy as np

    from progen_tpu.sampling import sample_fast

    refs = {}
    for pub, acc in originals.items():
        refs[pub] = np.asarray(sample_fast(
            jnp.asarray(acc["key"], jnp.uint32),
            workspace["model"], workspace["params"],
            jnp.asarray(acc["prime"], jnp.int32), acc["length"],
            top_k=acc["top_k"], add_bos=acc["add_bos"],
            temperature=acc["temperature"], top_p=acc["top_p"],
        ))
    for rid, ix, tok in tokens:
        assert rid in refs, f"token for unjournaled request {rid}"
        assert refs[rid][ix] == tok, (rid, ix, tok, int(refs[rid][ix]))


def _pump(proc, out_lines, err_lines, pred, timeout_s):
    """Drain both pipes into line lists until ``pred()`` or deadline.
    Raw-fd reads only — mixing buffered readline with a later drain
    strands complete lines inside the TextIOWrapper."""
    tails = getattr(proc, "_pump_tails", None)
    if tails is None:
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


def _run_fleet(workspace, tmp_path, *, replica_chaos=(), router_chaos="",
               n_requests=4, n_replicas=2):
    """Spawn replicas (per-replica chaos env) + a router, feed requests
    on the router's stdin, close intake, and run the fleet to drain.
    Returns (tokens, done, rejected, rdirs, replica_procs, router_err).
    """
    rdirs = [tmp_path / f"r{i}" for i in range(n_replicas)]
    chaos = list(replica_chaos) + [""] * (n_replicas - len(replica_chaos))
    procs = [
        _spawn_replica(workspace["ck"], rdir, chaos=c)
        for rdir, c in zip(rdirs, chaos)
    ]
    router = None
    try:
        _wait_sockets(list(zip(procs, rdirs)))
        router = _spawn_router(rdirs, chaos=router_chaos)
        router.stdin.write("\n".join(_requests(n_requests)) + "\n")
        # EOF closes intake; the router keeps polling until everything
        # it accepted has settled (including any handoffs), then exits
        router.stdin.close()
        out_lines, err_lines = [], []
        assert _pump(
            router, out_lines, err_lines,
            lambda: all(t[2] for t in router._pump_tails.values()), 600,
        ), (
            "router did not drain:\n"
            + "\n".join(err_lines)[-2000:]
        )
        router.wait(timeout=60)
        assert router.returncode == 0, "\n".join(err_lines)[-2000:]
        tokens, done, rejected = _parse_events(out_lines)
        return tokens, done, rejected, rdirs, procs, "\n".join(err_lines)
    finally:
        if router is not None and router.poll() is None:
            router.kill()
            router.wait()
        for p in procs:
            if p.poll() is None:
                p.terminate()
                p._sigterm_sent = True


def _stop_replica(proc, timeout_s=120):
    """Graceful SIGTERM drain; returns (stdout, stderr).

    One SIGTERM only: serve treats a second one as "exit now" (and a
    replica caught between drain and exit dies -15), so a process that
    ``_run_fleet`` already signalled is only waited on, never
    re-signalled — the drain it is running IS the graceful stop.
    """
    if proc.poll() is None and not getattr(proc, "_sigterm_sent", False):
        proc.terminate()
    proc._sigterm_sent = True
    return proc.communicate(timeout=timeout_s)


def _decode_compile_count(rdir):
    text = (Path(rdir) / "metrics.prom").read_text()
    m = re.search(
        r"^progen_serve_decode_compile_count (\S+)$", text, re.M
    )
    assert m, text
    return float(m.group(1))


class TestFleetKillMatrix:
    def test_replica_sigkill_mid_decode_fleet_recovers(
        self, workspace, tmp_path
    ):
        """The tier-1 failover case: replica 0 SIGKILLs at its 6th
        decode step with the fleet mid-stream. Exactly-once settlement,
        token dedup, bit-parity, a compile-flat survivor, and a
        replay-restart that resumes nothing."""
        tokens, done, rejected, rdirs, procs, _ = _run_fleet(
            workspace, tmp_path,
            replica_chaos=("serve/decode:kill@6",),
        )
        # the chaos rule really fired (invariant 6's contrapositive)
        assert procs[0].wait(timeout=60) == -9
        # 1: exactly once — all four answered, none twice, none shed
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        # 2: no (request, index) pair emitted twice across the fleet
        pairs = [(i, ix) for i, ix, _ in tokens]
        assert len(set(pairs)) == len(pairs)
        # the victim accepted work before dying and it was handed off
        victim_accepts = _journal_accepts(rdirs[0])
        assert victim_accepts, "kill@6 landed before any accept"
        from progen_tpu.serving.journal import (
            STATUS_HANDED_OFF,
            replay_requests,
        )
        from progen_tpu.telemetry.trace import iter_jsonl

        marks = [
            rec for rec in iter_jsonl(Path(rdirs[0]) / "journal.jsonl")
            if rec.get("op") == "done"
        ]
        assert any(m["status"] == STATUS_HANDED_OFF for m in marks)
        # 5 (fold view): ownership marks settle the dead journal
        pending, finished, n_done = replay_requests(
            Path(rdirs[0]) / "journal.jsonl"
        )
        assert pending == [] and finished == []
        assert n_done == len(victim_accepts)
        # 3: bit-parity against the uninterrupted reference streams
        originals = _original_accepts(rdirs)
        assert sorted(originals) == ["r0", "r1", "r2", "r3"]
        _assert_parity(workspace, originals, tokens)
        # 4: the survivor decoded fresh AND resumed work on ONE compile
        out1, err1 = _stop_replica(procs[1])
        assert procs[1].returncode == 0, err1[-2000:]
        assert _decode_compile_count(rdirs[1]) == 1.0
        assert "compile counts:" in err1
        # 5 (process view): a --replay restart of the victim resumes 0.
        # SIGKILL leaves the old socket file behind — remove it so the
        # wait below sees the REBORN process bind, not the stale inode
        (Path(rdirs[0]) / "serve.sock").unlink()
        reborn = _spawn_replica(workspace["ck"], rdirs[0], replay=True)
        try:
            _wait_sockets([(reborn, rdirs[0])])
            out3, err3 = _stop_replica(reborn)
        finally:
            if reborn.poll() is None:
                reborn.kill()
        assert reborn.returncode == 0, err3[-2000:]
        assert "replay: resumed 0 request(s)" in err3, err3[-2000:]


class TestTcpFleetKillMatrix:
    def test_replica_sigkill_mid_tcp_stream_fleet_recovers(
        self, workspace, tmp_path
    ):
        """The TCP twin of the tier-1 failover case: the victim serves
        framed TCP (``--tcp``), the survivor a unix socket, and the
        router fronts both in one fleet. Replica 0 SIGKILLs at its 6th
        decode step mid-TCP-stream; every accepted request must settle
        exactly once, the merged stream must stay bit-identical to the
        references (the frame envelope is payload-transparent), and a
        ``--replay`` rebirth of the victim must resume ZERO requests —
        the journal/handoff machinery is transport-blind."""
        rdirs = [tmp_path / "r0", tmp_path / "r1"]
        victim = _spawn_replica_tcp(
            workspace["ck"], rdirs[0], chaos="serve/decode:kill@6"
        )
        survivor = _spawn_replica(workspace["ck"], rdirs[1])
        router = None
        try:
            hostport = _wait_tcp_port(victim, rdirs[0])
            _wait_sockets([(survivor, rdirs[1])])
            router = _spawn_router_specs([
                f"tcp={hostport},journal={rdirs[0]},"
                f"prom={rdirs[0] / 'metrics.prom'}",
                f"sock={rdirs[1] / 'serve.sock'},journal={rdirs[1]},"
                f"prom={rdirs[1] / 'metrics.prom'}",
            ])
            router.stdin.write("\n".join(_requests(4)) + "\n")
            router.stdin.close()
            out_lines, err_lines = [], []
            assert _pump(
                router, out_lines, err_lines,
                lambda: all(
                    t[2] for t in router._pump_tails.values()
                ), 600,
            ), (
                "router did not drain:\n"
                + "\n".join(err_lines)[-2000:]
            )
            router.wait(timeout=60)
            assert router.returncode == 0, "\n".join(err_lines)[-2000:]
            tokens, done, rejected = _parse_events(out_lines)
        finally:
            if router is not None and router.poll() is None:
                router.kill()
                router.wait()
            for p in (victim, survivor):
                if p.poll() is None:
                    p.terminate()
        # the kill really landed mid-TCP-stream
        assert victim.wait(timeout=60) == -9
        # exactly once across the fleet, nothing shed, no dup tokens
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        pairs = [(i, ix) for i, ix, _ in tokens]
        assert len(set(pairs)) == len(pairs)
        victim_accepts = _journal_accepts(rdirs[0])
        assert victim_accepts, "kill@6 landed before any accept"
        from progen_tpu.serving.journal import replay_requests

        pending, finished, n_done = replay_requests(
            Path(rdirs[0]) / "journal.jsonl"
        )
        assert pending == [] and finished == []
        assert n_done == len(victim_accepts)
        # bit-parity: the TCP frames carried the exact JSONL payloads
        originals = _original_accepts(rdirs)
        assert sorted(originals) == ["r0", "r1", "r2", "r3"]
        _assert_parity(workspace, originals, tokens)
        survivor.wait(timeout=120)  # SIGTERM'd above: let it drain
        # a --replay rebirth over TCP resumes nothing: the router's
        # handed_off ownership marks make double-serving impossible
        reborn = _spawn_replica_tcp(
            workspace["ck"], rdirs[0], replay=True
        )
        try:
            _wait_tcp_port(reborn, rdirs[0], min_count=2)
            reborn.terminate()
            assert reborn.wait(timeout=120) == 0
        finally:
            if reborn.poll() is None:
                reborn.kill()
        log = (rdirs[0] / "stderr.log").read_text()
        assert "replay: resumed 0 request(s)" in log, log[-2000:]


@pytest.mark.slow
class TestFleetKillMatrixSlow:
    def test_replica_sigkill_mid_prefill(self, workspace, tmp_path):
        """Die inside a prefill: accepted-but-barely-started requests
        must hand off (or re-dispatch) without loss."""
        tokens, done, rejected, rdirs, procs, _ = _run_fleet(
            workspace, tmp_path,
            replica_chaos=("serve/prefill_chunk:kill@2",),
        )
        assert procs[0].wait(timeout=60) == -9
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        pairs = [(i, ix) for i, ix, _ in tokens]
        assert len(set(pairs)) == len(pairs)
        _assert_parity(workspace, _original_accepts(rdirs), tokens)

    def test_handoff_site_fault_does_not_lose_work(
        self, workspace, tmp_path
    ):
        """A transient ChaosError at the router's own handoff span
        (router/handoff:fail@1) must be absorbed — the fold is
        idempotent and retried, so the kill still loses nothing."""
        tokens, done, rejected, rdirs, procs, _ = _run_fleet(
            workspace, tmp_path,
            replica_chaos=("serve/decode:kill@6",),
            router_chaos="router/handoff:fail@1",
        )
        assert procs[0].wait(timeout=60) == -9
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        pairs = [(i, ix) for i, ix, _ in tokens]
        assert len(set(pairs)) == len(pairs)
        _assert_parity(workspace, _original_accepts(rdirs), tokens)

    def test_dispatch_site_fault_is_retried(self, workspace, tmp_path):
        """A transient fault on the dispatch write path re-routes on
        the backoff schedule instead of dropping the request."""
        tokens, done, rejected, rdirs, _, _ = _run_fleet(
            workspace, tmp_path,
            router_chaos="router/dispatch:fail@2",
        )
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        _assert_parity(workspace, _original_accepts(rdirs), tokens)

    @pytest.mark.parametrize("n", [3, 9])
    def test_decode_kill_sweep_bit_parity(self, workspace, tmp_path, n):
        """Sweep the kill point across the victim's decode timeline;
        the fleet's merged token stream stays bit-identical to the
        uninterrupted references."""
        tokens, done, rejected, rdirs, procs, _ = _run_fleet(
            workspace, tmp_path,
            replica_chaos=(f"serve/decode:kill@{n}",),
        )
        assert procs[0].wait(timeout=60) == -9
        assert sorted(done) == ["r0", "r1", "r2", "r3"]
        assert rejected == []
        pairs = [(i, ix) for i, ix, _ in tokens]
        assert len(set(pairs)) == len(pairs)
        _assert_parity(workspace, _original_accepts(rdirs), tokens)


class TestRouterChaosTargets:
    def test_router_targets_are_known(self):
        from progen_tpu.resilience import chaos

        for target in ("router/connect", "router/dispatch",
                       "router/handoff"):
            assert target in chaos.KNOWN_TARGETS
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            chaos.install("router/dispatch:fail@999")
        chaos.uninstall()

    def test_unknown_router_target_still_warns_once(self):
        from progen_tpu.resilience import chaos

        chaos._WARNED_UNKNOWN.discard("router/bogus")
        try:
            with pytest.warns(UserWarning, match="router/bogus"):
                # deliberately-unknown target: the warn-once under test
                chaos.install("router/bogus:fail@99")  # progen: ignore[PGL009]
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                # second install: silent (warn-once)
                chaos.install("router/bogus:fail@99")  # progen: ignore[PGL009]
        finally:
            chaos.uninstall()

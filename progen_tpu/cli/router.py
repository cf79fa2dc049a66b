"""Router CLI — elastic multi-replica serving front-end.

Fans the same JSONL request protocol cli/serve.py speaks across N
serve replicas (each `cli/serve --socket ... --journal_dir ...` with
its OWN journal), and survives replica death by journal-ownership
handoff (serving/router.py). Two ways to get a fleet:

  * point at running replicas:

        progen-tpu-router \
          --replica sock=/run/r0.sock,journal=/var/r0,prom=/var/r0/m.prom \
          --replica sock=/run/r1.sock,journal=/var/r1,prom=/var/r1/m.prom

  * or spawn one (dev/smoke): ``--spawn 2 --checkpoint_path ./ckpts
    --fleet_dir ./fleet`` starts two serve subprocesses with per-replica
    socket/journal/prom files under ``fleet_dir/replica{i}/``;
    ``--respawn`` restarts a dead replica with ``--replay`` of its own
    journal — safe against double-serving because the handoff writes
    ``handed_off`` ownership marks BEFORE any restart can replay.

Requests arrive on stdin (default), a unix socket (--socket PATH), or
framed TCP (--listen_tcp HOST:PORT — fleet/transport.py), exactly as
cli/serve.py: one JSON object per line, ``id`` required, optional
``tenant`` for per-tenant quotas. Token/done/rejected events stream
back interleaved. Replicas may be remote too: ``--replica
tcp=HOST:PORT,...`` dials the framed transport a ``serve --tcp``
process listens on. Shedding reasons the router adds on top of the
replica's: ``router_queue_full``, ``tenant_quota``, ``draining``,
``no_replicas``, ``replica_lost``.

AUTOSCALING (fleet/autoscaler.py): ``--autoscale POLICY.toml
--autoscale_tsdb DIR`` runs a policy tick against the fleet
collector's ring TSDB inside the spawned-fleet loop. Scale-up revives
the lowest retired replica slot (or grows the fleet) and spawns its
serve process with ``--replay`` of its own journal; scale-down retires
the highest live index — no new work, queued requests released back to
the router (journaled ``handed_off``), SIGTERM once its slots drain
(or on the grace deadline; the EOF rides the normal handoff path
either way, so accepted work is never lost). Every up/down decision
(and each hold-reason change) lands as an ``ev:"scale"`` record in the
router's events.jsonl.

SIGTERM/SIGINT drains: intake closes, queued requests are shed with
reason ``draining``, in-flight streams (and any handoffs their
replicas' deaths force) run to completion, then exit 0. A second
signal kills immediately (open request tracks are closed with reason
``killed`` first, so the post-mortem trace is honest).

Router metrics render under the ``progen_router_`` Prometheus prefix
(--prom_file / --prom_port) and land in the tracker under ``router/``.

Run: python -m progen_tpu.cli.router --spawn 2 --checkpoint_path ./ckpts
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file(compile_counters=False)  # env flags only: this tool stays jax-free

import json
import os
import select
import signal
import socket as socketlib
import subprocess
import sys
import time

import click


def _host_chips() -> list:
    """The TPU chips this process may hand to spawned replicas, as
    ``TPU_VISIBLE_CHIPS`` indices; [] on a host without chips. Read from
    the environment and the device files, never from jax: a chip belongs
    to one process at a time, and that process is a replica — the router
    must not initialise a backend. Older VM images expose chips as
    /dev/accel<N>, newer ones as numbered VFIO groups /dev/vfio/<N>."""
    import glob

    pinned = os.environ.get("TPU_VISIBLE_CHIPS")
    if pinned:
        return [c.strip() for c in pinned.split(",") if c.strip()]
    n = len(glob.glob("/dev/accel[0-9]*")) + sum(
        os.path.basename(p).isdigit() for p in glob.glob("/dev/vfio/*")
    )
    return [str(i) for i in range(n)]


def _replica_env(i: int, chips: list):
    """Environment for spawned replica ``i``: on a chip host, exactly one
    chip of its own (with the parent's environment unchanged every
    replica would try to claim every chip, and all but the first would
    fail or hang); on a host without chips, the parent's environment."""
    if not chips:
        return None
    if i >= len(chips):
        raise click.ClickException(
            f"replica{i} needs a chip of its own, but this host exposes "
            f"{len(chips)} ({', '.join(chips)}): one process per chip"
        )
    return {
        **os.environ,
        "TPU_VISIBLE_CHIPS": chips[i],
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@click.command()
@click.option("--replica", "replica_specs", multiple=True,
              help="replica endpoint, repeatable: 'sock=PATH' or "
                   "'tcp=HOST:PORT', plus "
                   "'[,journal=DIR][,prom=FILE][,name=N]', or a "
                   "bare socket path (no journal = no handoff, only "
                   "re-dispatch of never-accepted requests)")
@click.option("--spawn", default=0,
              help="spawn N serve replicas under --fleet_dir instead of "
                   "connecting to --replica endpoints")
@click.option("--checkpoint_path", default="./ckpts",
              help="checkpoint for spawned replicas")
@click.option("--fleet_dir", default="./fleet", type=str,
              help="per-replica socket/journal/prom/log files land in "
                   "FLEET_DIR/replica{i}/")
@click.option("--respawn/--no-respawn", default=False,
              help="restart a dead spawned replica with --replay of its "
                   "own journal (handed-off work is skipped via its "
                   "ownership marks)")
@click.option("--replica-max-slots", default=8,
              help="--max-slots for spawned replicas")
@click.option("--replica-max-queue", default=64,
              help="--max-queue for spawned replicas")
@click.option("--max-len", default=None, type=int,
              help="--max-len for spawned replicas")
@click.option("--replica_reload_watch", default=0.0, type=float,
              help="spawned replicas watch their checkpoint dir every N "
                   "seconds (serve --reload_watch) and honor a "
                   "FLEET_DIR/replica{i}/reload.pin control file "
                   "(serve --reload_pin) — the deploy controller's "
                   "per-replica seam (0 = off)")
@click.option("--replica_profile_watch", default=False, is_flag=True,
              help="spawned replicas watch "
                   "FLEET_DIR/replica{i}/profile.pin (serve "
                   "--profile_pin) for on-demand jax.profiler windows "
                   "and arm their flight recorders (dumps to "
                   "replica{i}/flight/) — the collector's auto-profile "
                   "and crash-forensics seam, per replica")
@click.option("--flight_dir", default=None, type=str,
              help="arm the ROUTER's own flight recorder: bounded ring "
                   "of recent routing telemetry, dumped atomically here "
                   "on crash paths")
@click.option("--max-queue", default=256,
              help="router admission queue bound (shed reason "
                   "'router_queue_full' beyond it)")
@click.option("--tenant_quota", default=0,
              help="max outstanding requests per 'tenant' field "
                   "(0 = unlimited; shed reason 'tenant_quota')")
@click.option("--heartbeat_timeout", default=30.0, type=float,
              help="deprioritize a replica whose prom-file heartbeat is "
                   "older than this many seconds")
@click.option("--socket", "socket_path", default=None, type=str,
              help="serve a unix domain socket at PATH instead of "
                   "stdin/stdout")
@click.option("--listen_tcp", default=None, type=str,
              help="serve framed TCP at HOST:PORT (fleet transport; "
                   "PORT 0 = ephemeral, bound port printed on stderr)")
@click.option("--autoscale", "autoscale_policy", default=None, type=str,
              help="autoscale the --spawn fleet from the [autoscaler] "
                   "table of this TOML policy file (fleet/autoscaler.py)")
@click.option("--autoscale_tsdb", default=None, type=str,
              help="the fleet collector's ring-TSDB directory the "
                   "autoscaler reads its signals from (required with "
                   "--autoscale)")
@click.option("--metrics-every", default=0,
              help="log a router/ metrics snapshot (and rewrite "
                   "--prom_file) every N loop ticks (0 = only at exit)")
@click.option("--prom_file", default=None, type=str,
              help="write progen_router_* Prometheus text here")
@click.option("--prom_port", default=0,
              help="serve progen_router_* metrics over HTTP on this "
                   "localhost port (0 = off)")
def main(replica_specs, spawn, checkpoint_path, fleet_dir, respawn,
         replica_max_slots, replica_max_queue, max_len,
         replica_reload_watch, replica_profile_watch, flight_dir,
         max_queue, tenant_quota,
         heartbeat_timeout, socket_path, listen_tcp,
         autoscale_policy, autoscale_tsdb, metrics_every,
         prom_file, prom_port):
    from progen_tpu import telemetry
    from progen_tpu.resilience.chaos import ChaosError, install_from_env
    from progen_tpu.serving.router import Router, parse_replica_spec
    from progen_tpu.telemetry import (
        prometheus_text,
        start_prometheus_server,
        write_prometheus,
    )
    from progen_tpu.tracking import make_tracker

    # router chaos sites (router/connect, router/dispatch,
    # router/handoff) arm from the environment, same as cli/serve.py
    install_from_env()

    if spawn and replica_specs:
        sys.exit("use --spawn or --replica, not both")
    if not spawn and not replica_specs:
        sys.exit("no fleet: pass --replica specs or --spawn N")
    if autoscale_policy and not spawn:
        sys.exit("--autoscale needs --spawn (the router must own the "
                 "replica processes it scales)")
    if autoscale_policy and not autoscale_tsdb:
        sys.exit("--autoscale needs --autoscale_tsdb DIR (the fleet "
                 "collector's TSDB is the policy's signal source)")

    procs = {}  # replica index -> (Popen, replica_dir, log file)
    chips = _host_chips()

    def _spawn_replica(i, replay=False):
        env = _replica_env(i, chips)
        rdir = os.path.join(fleet_dir, f"replica{i}")
        os.makedirs(rdir, exist_ok=True)
        args = [
            sys.executable, "-m", "progen_tpu.cli.serve",
            "--checkpoint_path", checkpoint_path,
            "--socket", os.path.join(rdir, "serve.sock"),
            "--journal_dir", rdir,
            "--prom_file", os.path.join(rdir, "metrics.prom"),
            "--metrics-every", "4",
            "--max-slots", str(replica_max_slots),
            "--max-queue", str(replica_max_queue),
        ]
        if max_len is not None:
            args += ["--max-len", str(max_len)]
        if replica_reload_watch:
            args += [
                "--reload_watch", str(replica_reload_watch),
                "--reload_pin", os.path.join(rdir, "reload.pin"),
            ]
        if replica_profile_watch:
            args += [
                "--profile_pin", os.path.join(rdir, "profile.pin"),
                "--flight_dir", os.path.join(rdir, "flight"),
            ]
        if replay:
            args += ["--replay", rdir]
        log = open(os.path.join(rdir, "replica.log"), "ab")
        proc = subprocess.Popen(
            args, stdin=subprocess.DEVNULL, stdout=log, stderr=log,
            env=env,
        )
        procs[i] = (proc, rdir, log)
        print(
            f"replica{i}: pid {proc.pid}"
            + (f" on chip {chips[i]}" if chips else "")
            + (" (replaying its journal)" if replay else ""),
            file=sys.stderr,
        )

    def _spawned_spec(i):
        rdir = os.path.join(fleet_dir, f"replica{i}")
        return parse_replica_spec(
            f"sock={os.path.join(rdir, 'serve.sock')},"
            f"journal={rdir},"
            f"prom={os.path.join(rdir, 'metrics.prom')}"
        )

    if spawn:
        specs = []
        for i in range(spawn):
            specs.append(_spawned_spec(i))
            _spawn_replica(i)
    else:
        specs = [parse_replica_spec(s) for s in replica_specs]

    router = Router(
        specs, max_queue=max_queue, tenant_quota=tenant_quota,
        heartbeat_timeout=heartbeat_timeout,
    )
    tracker = make_tracker("progen-router")
    telemetry.configure(sink=tracker.log_event)
    from progen_tpu.telemetry import flight as flight_mod
    if flight_dir:
        flight_mod.arm(flight_dir, metrics_fn=router.metrics.snapshot)
    run_dir = getattr(tracker, "path", None)
    if run_dir is not None:
        print(
            f"router traces: {run_dir}/events.jsonl "
            "(render with progen-tpu-telemetry export-trace)",
            file=sys.stderr,
        )

    def publish(step=None):
        router.metrics.log_to(tracker, step=step, prefix="router/")
        if prom_file:
            write_prometheus(
                prom_file,
                prometheus_text(router.metrics, prefix="progen_router_"),
            )

    prom_srv = None
    if prom_port:
        prom_srv = start_prometheus_server(
            lambda: prometheus_text(
                router.metrics, prefix="progen_router_"
            ),
            port=prom_port,
        )
        print(
            f"prometheus on http://127.0.0.1:"
            f"{prom_srv.server_address[1]}/metrics",
            file=sys.stderr,
        )
    print(
        f"routing across {len(specs)} replica(s): "
        + ", ".join(s.endpoint for s in specs),
        file=sys.stderr,
    )

    # ----- autoscaler executor (fleet/autoscaler.py decides, this
    # closure acts on the spawned fleet) -------------------------------
    autoscale_fn = None
    scale_state = {"next": 0.0, "draining": {}}  # index -> grace deadline
    if autoscale_policy:
        from progen_tpu.fleet.autoscaler import (
            ACTION_DOWN,
            ACTION_UP,
            Autoscaler,
            load_policy,
        )
        from progen_tpu.telemetry.tsdb import TsdbReader

        policy = load_policy(autoscale_policy)
        scaler = Autoscaler(policy, reader=TsdbReader(autoscale_tsdb))
        router.rebalance_max = policy.rebalance_max
        # a retiring replica gets this long to finish its decode slots
        # before SIGTERM stops waiting (SIGTERM itself is still a
        # graceful drain on the serve side)
        drain_grace_s = max(10.0, policy.interval_s * 5)
        print(
            f"autoscaler: {policy.min_replicas}..{policy.max_replicas} "
            f"replicas, tick {policy.interval_s}s, tsdb {autoscale_tsdb}",
            file=sys.stderr,
        )

        def _scale_up(n):
            for _ in range(n):
                reusable = sorted(
                    link.index for link in router.links
                    if link.retired and link.index not in procs
                    and link.index not in scale_state["draining"]
                )
                if reusable:
                    i = reusable[0]
                    router.revive_replica(i)
                else:
                    i = router.add_replica(_spawned_spec(len(router.links)))
                # --replay unconditionally: a no-op on a fresh journal,
                # and on a reused slot it resumes whatever the handoff
                # didn't settle (the handed_off ownership marks make
                # double-serving impossible)
                _spawn_replica(i, replay=True)

        def _scale_down(n, now):
            live = sorted(
                (link.index for link in router.links if not link.retired),
                reverse=True,
            )
            for i in live[:n]:
                router.retire_replica(i)
                scale_state["draining"][i] = now + drain_grace_s
                print(f"replica{i}: retiring (scale-down)",
                      file=sys.stderr)

        def _reap_draining(now):
            for i, deadline in list(scale_state["draining"].items()):
                entry = procs.get(i)
                if entry is None:
                    # already exited; tick() reaped the process
                    scale_state["draining"].pop(i)
                    continue
                if router.links[i].inflight and now < deadline:
                    continue  # still streaming: let it finish
                # SIGTERM = serve's graceful drain (in-flight slots run
                # to completion, journal/metrics flush, exit 0). What it
                # rejects as 'draining' the router re-routes; if it dies
                # instead, the EOF rides the normal handoff path. Zero
                # accepted requests lost either way.
                entry[0].terminate()
                scale_state["draining"].pop(i)

        def _autoscale_tick():
            now = time.monotonic()
            _reap_draining(now)
            if now < scale_state["next"]:
                return
            scale_state["next"] = now + policy.interval_s
            n_current = sum(
                1 for link in router.links if not link.retired
            )
            try:
                decision = scaler.decide(n_current)
            except ChaosError:
                # autoscaler/decide chaos: a transient fault costs one
                # tick, never the fleet
                return
            if decision.action == ACTION_UP:
                _scale_up(decision.target - n_current)
            elif decision.action == ACTION_DOWN:
                _scale_down(n_current - decision.target, now)

        autoscale_fn = _autoscale_tick

    shutdown = {"flag": False}

    def _request_drain(signum, frame):
        if shutdown["flag"]:
            print(f"signal {signum} again: exiting now", file=sys.stderr)
            try:
                router.close_tracks("killed")
            except Exception:
                pass  # a torn trace line beats a hung exit
            flight_mod.dump_now("killed", note=f"signal {signum}")
            sys.stderr.flush()
            os._exit(1)
        shutdown["flag"] = True
        print(
            f"signal {signum}: draining — intake closed, queued requests "
            "shed, in-flight streams finishing; signal again to kill",
            file=sys.stderr,
        )

    def tick():
        """Once per front-loop iteration, AFTER router.poll() — so a
        dead spawned replica's handoff (triggered by the socket EOF
        inside poll) has already written its ownership marks before any
        --respawn replay can read the journal."""
        if shutdown["flag"]:
            return
        for i, (proc, rdir, log) in list(procs.items()):
            if proc.poll() is None:
                continue
            del procs[i]
            log.close()
            print(
                f"replica{i}: exited rc={proc.returncode}",
                file=sys.stderr,
            )
            # a retired replica's exit is the scale-down completing,
            # not a death to heal
            if respawn and not router.links[i].up \
                    and not router.links[i].retired:
                _spawn_replica(i, replay=True)
        if autoscale_fn is not None:
            autoscale_fn()

    old_term = signal.signal(signal.SIGTERM, _request_drain)
    old_int = signal.signal(signal.SIGINT, _request_drain)
    try:
        if listen_tcp:
            _front_tcp(router, listen_tcp, publish, metrics_every,
                       shutdown, tick=tick)
        elif socket_path:
            _front_socket(router, socket_path, publish, metrics_every,
                          shutdown, tick=tick)
        else:
            _front_stdio(router, publish, metrics_every, shutdown,
                         tick=tick)
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
        publish()
        if prom_srv is not None:
            prom_srv.shutdown()
        for i, (proc, rdir, log) in procs.items():
            proc.terminate()
        for i, (proc, rdir, log) in procs.items():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
            log.close()
        flight_mod.disarm()
        telemetry.configure()  # detach before the sink closes
        tracker.finish()


def _submit_obj(router, line, client=None):
    """Parse + submit one request line; returns a rejection event dict
    to answer immediately, or None."""
    try:
        obj = json.loads(line)
        if not isinstance(obj, dict):
            raise ValueError("request must be a JSON object")
    except ValueError as e:
        return {"event": "rejected", "id": None,
                "reason": f"bad request line: {e}"}
    return router.submit(obj, client=client)


def _front_stdio(router, publish, metrics_every, shutdown, tick=None):
    """stdin-JSONL front: one select loop over {stdin, replica sockets}
    — new requests and replica events interleave without polling sleeps.
    Same raw-fd line buffering as cli/serve.py (select()+readline()
    loses lines). EOF or a drain signal closes intake; the loop runs
    until the router settles everything it accepted."""
    out = sys.stdout
    eof = False
    drained = False
    buf = ""
    ticks = 0

    def emit(ev):
        out.write(json.dumps(ev) + "\n")
        out.flush()

    while True:
        if shutdown["flag"] and not drained:
            drained = True
            router.drain()
        if (eof or shutdown["flag"]) and not router.has_work:
            break
        rlist = ([] if (eof or shutdown["flag"]) else [sys.stdin])
        rlist += router.fds()
        # bounded wait: backoffs/reconnects need the loop to turn even
        # when no fd is hot
        timeout = 0.05 if router.has_work else 0.2
        try:
            if rlist:
                select.select(rlist, [], [], timeout)
        except OSError:
            pass  # a replica socket died between fds() and select
        while not eof and not shutdown["flag"]:
            nl = buf.find("\n")
            if nl < 0:
                try:
                    ready, _, _ = select.select([sys.stdin], [], [], 0.0)
                except OSError:
                    break
                if not ready:
                    break
                data = os.read(sys.stdin.fileno(), 65536)
                if not data:
                    eof = True
                    line, buf = buf, ""
                else:
                    buf += data.decode("utf-8", errors="replace")
                    continue
            else:
                line, buf = buf[:nl], buf[nl + 1:]
            if not line.strip():
                continue
            rej = _submit_obj(router, line)
            if rej is not None:
                emit(rej)
        for _, ev in router.poll():
            emit(ev)
        if tick is not None:
            tick()
        ticks += 1
        if metrics_every and ticks % metrics_every == 0:
            publish(ticks)


def _front_socket(router, socket_path, publish, metrics_every, shutdown,
                  tick=None):
    """Unix-socket front: each connection submits requests and receives
    exactly its own events (the router's per-request ``client`` handle
    is the connection fd). On drain the listener closes, the queue is
    shed, in-flight streams finish to their clients, then exit."""
    if os.path.exists(socket_path):
        os.unlink(socket_path)
    srv = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
    srv.bind(socket_path)
    srv.listen(16)
    srv.setblocking(False)
    clients = {}  # fd -> (sock, recv_buffer)
    ticks = 0
    drained = False
    print(f"listening on {socket_path}", file=sys.stderr)

    def send(fd, ev):
        sock, _ = clients.get(fd, (None, None))
        if sock is None:
            return
        try:
            sock.sendall(json.dumps(ev).encode() + b"\n")
        except OSError:
            _drop(fd)

    def _drop(fd):
        sock, _ = clients.pop(fd, (None, None))
        if sock is not None:
            sock.close()

    try:
        while True:
            if shutdown["flag"] and not drained:
                drained = True
                srv.close()  # refuse new connections during drain
                router.drain()
            if shutdown["flag"] and not router.has_work:
                break
            rlist = ([] if drained else [srv])
            rlist += [s for s, _ in clients.values()]
            rlist += router.fds()
            timeout = 0.05 if router.has_work else 0.2
            try:
                ready, _, _ = (
                    select.select(rlist, [], [], timeout)
                    if rlist else ([], [], [])
                )
            except OSError:
                continue  # a peer vanished between list and select
            replica_socks = set(router.fds())
            for sock in ready:
                if sock is srv:
                    conn, _ = srv.accept()
                    conn.setblocking(False)
                    clients[conn.fileno()] = (conn, b"")
                    continue
                if sock in replica_socks:
                    continue  # router.poll() below reads these
                fd = sock.fileno()
                if fd not in clients:
                    continue
                try:
                    data = sock.recv(65536)
                except OSError:
                    data = b""
                if not data:
                    _drop(fd)
                    continue
                _, cbuf = clients[fd]
                cbuf += data
                *lines, cbuf = cbuf.split(b"\n")
                clients[fd] = (sock, cbuf)
                for raw in lines:
                    if not raw.strip():
                        continue
                    rej = _submit_obj(
                        router, raw.decode("utf-8", "replace"), client=fd
                    )
                    if rej is not None:
                        send(fd, rej)
            for client, ev in router.poll():
                if client is not None:
                    send(client, ev)
            if tick is not None:
                tick()
            ticks += 1
            if metrics_every and ticks % metrics_every == 0:
                publish(ticks)
    finally:
        for fd in list(clients):
            _drop(fd)
        srv.close()
        if os.path.exists(socket_path):
            os.unlink(socket_path)


def _front_tcp(router, hostport, publish, metrics_every, shutdown,
               tick=None):
    """Framed-TCP front (fleet/transport.py): the unix-socket front
    with frames instead of newlines. Each connection submits requests
    and receives exactly its own events; a framing violation reads as
    EOF and drops only that client."""
    from progen_tpu.fleet.transport import FramedListener, parse_hostport

    host, port = parse_hostport(hostport)
    listener = FramedListener(host, port)
    clients = {}  # fd -> FramedConnection
    ticks = 0
    drained = False
    print(f"listening on tcp {listener.host}:{listener.port}",
          file=sys.stderr)
    sys.stderr.flush()

    def send(fd, ev):
        conn = clients.get(fd)
        if conn is None:
            return
        try:
            conn.send_line(json.dumps(ev))
        except OSError:
            _drop(fd)

    def _drop(fd):
        conn = clients.pop(fd, None)
        if conn is not None:
            conn.close()

    try:
        while True:
            if shutdown["flag"] and not drained:
                drained = True
                listener.close()  # refuse new dials during drain
                router.drain()
            if shutdown["flag"] and not router.has_work:
                break
            rlist = ([] if drained else [listener])
            rlist += list(clients.values())
            rlist += router.fds()
            timeout = 0.05 if router.has_work else 0.2
            try:
                ready, _, _ = (
                    select.select(rlist, [], [], timeout)
                    if rlist else ([], [], [])
                )
            except OSError:
                continue  # a peer vanished between list and select
            replica_socks = set(router.fds())
            for obj in ready:
                if obj is listener:
                    conn = listener.accept()
                    if conn is not None:
                        clients[conn.fileno()] = conn
                    continue
                if obj in replica_socks:
                    continue  # router.poll() below reads these
                if getattr(obj, "sock", None) is None:
                    continue  # dropped earlier this iteration
                fd = obj.fileno()
                if fd not in clients:
                    continue
                lines, eof = obj.recv_lines()
                for line in lines:
                    if not line.strip():
                        continue
                    rej = _submit_obj(router, line, client=fd)
                    if rej is not None:
                        send(fd, rej)
                if eof:
                    _drop(fd)
            for client, ev in router.poll():
                if client is not None:
                    send(client, ev)
            if tick is not None:
                tick()
            ticks += 1
            if metrics_every and ticks % metrics_every == 0:
                publish(ticks)
    finally:
        for fd in list(clients):
            _drop(fd)
        listener.close()


if __name__ == "__main__":
    main()

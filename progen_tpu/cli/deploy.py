"""Deploy controller CLI — continuous deployment for a serve fleet.

Watches a checkpoint directory and runs the canary → probe → promote →
converge pipeline (progen_tpu/deploy/controller.py) against a fleet of
replicas that honor ``reload.pin`` control files (serve
``--reload_pin``, or router ``--spawn --replica_reload_watch``). Every
decision lands in the fsync'd ``deploy.jsonl`` ledger under
``--deploy_dir``; kill the controller at any phase and a restart
replays the ledger and resumes idempotently.

Point it at a spawned fleet's directory (replicas discovered as
``FLEET_DIR/replica*/``):

    progen-tpu-deploy --checkpoint_path ./ckpts --fleet_dir ./fleet \\
        --probe_fasta probe.fasta --policy configs/serving/deploy.toml \\
        --tsdb ./tsdb --alerts ./fleet/alerts.jsonl

or name replicas explicitly with ``--replica name=DIR`` (DIR holds the
replica's reload.pin / reload.pin.ack). Start the controller BEFORE
publishing candidate checkpoints: its adopt step pins every replica to
the current fleet checkpoint, so no replica's newest-wins watcher can
self-upgrade past the canary gate.

Rollbacks page through the alert pipeline: ``--alerts`` appends
``deploy_rollback`` alerts to an AlertSink ledger (edge-deduped =
exactly-once per checkpoint across restarts) and ``--alert_config``
additionally routes them (webhook/stderr/file + escalation chains,
telemetry/alert_router.py).

Run: python -m progen_tpu.cli.deploy --checkpoint_path ./ckpts \\
         --fleet_dir ./fleet --once
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file(compile_counters=False)  # env flags only: this tool stays jax-free

import glob
import os
import signal
import sys
import time

import click


@click.command()
@click.option("--checkpoint_path", default="./ckpts",
              help="the checkpoint dir the trainer publishes into")
@click.option("--fleet_dir", default=None, type=str,
              help="discover replicas as FLEET_DIR/replica*/ (the "
                   "router --spawn layout)")
@click.option("--replica", "replica_specs", multiple=True,
              help="explicit replica, repeatable: 'name=DIR' (DIR "
                   "holds reload.pin/reload.pin.ack)")
@click.option("--deploy_dir", default=None, type=str,
              help="ledger + probe outputs land here (default: "
                   "FLEET_DIR/deploy)")
@click.option("--probe_fasta", default=None, type=str,
              help="held-out probe set; without it the probe/ppl gate "
                   "is skipped (canary ack alone gates promotion)")
@click.option("--policy", "policy_path", default=None, type=str,
              help="[deploy] TOML policy (configs/serving/deploy.toml)")
@click.option("--tsdb", default=None, type=str,
              help="the fleet collector's ring-TSDB dir (live ttft "
                   "baseline; optional)")
@click.option("--alerts", "alerts_path", default=None, type=str,
              help="append deploy_rollback alerts to this AlertSink "
                   "ledger (alerts.jsonl)")
@click.option("--alert_config", default=None, type=str,
              help="route alerts through this [route_*] TOML "
                   "(webhooks/escalation; needs --alerts)")
@click.option("--canary", default=None, type=str,
              help="canary replica name (overrides the policy; "
                   "default: first replica)")
@click.option("--interval", default=None, type=float,
              help="tick cadence in seconds (overrides the policy)")
@click.option("--once", is_flag=True, default=False,
              help="one tick, then exit (smoke/CI)")
@click.option("--flight_dir", default=None, type=str,
              help="arm the deploy controller's flight recorder: "
                   "bounded ring of recent deploy telemetry, dumped "
                   "atomically here on crash paths and on anomaly "
                   "rollback")
@click.option("--max_ticks", default=0,
              help="exit after N ticks (0 = run until signalled)")
def main(checkpoint_path, fleet_dir, replica_specs, deploy_dir,
         probe_fasta, policy_path, tsdb, alerts_path, alert_config,
         canary, interval, once, flight_dir, max_ticks):
    import dataclasses

    from progen_tpu import telemetry
    from progen_tpu.deploy import (
        DeployController,
        DeployPolicy,
        Replica,
        load_deploy_policy,
    )
    from progen_tpu.resilience.chaos import install_from_env
    from progen_tpu.tracking import make_tracker

    # deploy chaos sites (deploy/canary, deploy/probe, deploy/promote,
    # deploy/rollback) arm from the environment, same as cli/serve.py
    install_from_env()

    replicas = []
    for spec in replica_specs:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            sys.exit(f"bad --replica {spec!r}: expected name=DIR")
        replicas.append(Replica(name, path))
    if fleet_dir:
        for rdir in sorted(glob.glob(os.path.join(fleet_dir, "replica*"))):
            if os.path.isdir(rdir):
                replicas.append(Replica(os.path.basename(rdir), rdir))
    if not replicas:
        sys.exit("no replicas: pass --fleet_dir or --replica name=DIR")
    if deploy_dir is None:
        if not fleet_dir:
            sys.exit("--deploy_dir is required without --fleet_dir")
        deploy_dir = os.path.join(fleet_dir, "deploy")

    policy = (
        load_deploy_policy(policy_path) if policy_path
        else DeployPolicy()
    )
    if canary is not None:
        policy = dataclasses.replace(policy, canary=canary)
    tick_s = policy.interval_s if interval is None else float(interval)

    reader = None
    if tsdb is not None:
        from progen_tpu.telemetry.tsdb import TsdbReader

        reader = TsdbReader(tsdb)
    alerts = None
    router = None
    if alert_config is not None and alerts_path is None:
        sys.exit("--alert_config needs --alerts (the sink the router "
                 "relays from)")
    if alerts_path is not None:
        from progen_tpu.telemetry.alerts import AlertSink

        if alert_config is not None:
            from progen_tpu.telemetry.alert_router import (
                AlertRouter,
                load_router_config,
            )

            severity, routes = load_router_config(alert_config)
            router = AlertRouter(
                os.path.join(
                    os.path.dirname(alerts_path) or ".",
                    "notifications.jsonl",
                ),
                routes, severity=severity,
            )
        alerts = AlertSink(
            alerts_path,
            relay=router.handle if router is not None else None,
        )

    tracker = make_tracker("progen-deploy")
    telemetry.configure(sink=tracker.log_event)
    from progen_tpu.telemetry import flight as flight_mod
    if flight_dir:
        flight_mod.arm(flight_dir)
    ctrl = DeployController(
        checkpoint_path, replicas, deploy_dir, policy,
        probe_fasta=probe_fasta, reader=reader, alerts=alerts,
    )
    click.echo(
        f"deploy: {len(replicas)} replica(s), canary "
        f"{ctrl.canary.name}, ledger {ctrl.ledger.path}"
        + (f", probe {probe_fasta}" if probe_fasta else ", no probe")
        + (f", tsdb {tsdb}" if tsdb else ""),
        err=True,
    )

    stop = {"flag": False}

    def _stop(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    ticks = 0
    ops = {"rollback": 0, "converged": 0}
    try:
        while not stop["flag"]:
            op = ctrl.tick()
            if op is not None:
                click.echo(
                    f"deploy: {op} "
                    f"(fleet {ctrl.state.fleet}, "
                    f"candidate {ctrl.state.candidate})",
                    err=True,
                )
                if op in ops:
                    ops[op] += 1
            if router is not None:
                router.tick()
            ticks += 1
            if once or (max_ticks and ticks >= max_ticks):
                break
            deadline = time.time() + tick_s
            while not stop["flag"] and time.time() < deadline:
                time.sleep(min(0.2, tick_s))
    finally:
        ctrl.close()
        if alerts is not None:
            alerts.close()
        if router is not None:
            router.close()
        flight_mod.disarm()
        telemetry.configure()  # detach before the sink closes
        tracker.finish()
    click.echo(
        f"deploy: {ticks} ticks, fleet {ctrl.state.fleet}, "
        f"{ops['converged']} converged, {ops['rollback']} rolled back",
        err=True,
    )
    sys.exit(0)


if __name__ == "__main__":
    main()

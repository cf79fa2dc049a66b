"""PGL006 true negatives: expected findings: 0."""


def literal_span(telemetry):
    with telemetry.span("data/load", shard=3):  # varying data in attrs
        pass


def forwarding_wrapper(telemetry, name):
    return telemetry.span(name)  # forwarded own param: the spans.py idiom


def clean_metrics(reg):
    reg.inc("tokens_total")
    reg.observe("step_seconds", 0.5)
    reg.set_gauges({"hbm_bytes_in_use": 1, "hbm_bytes_limit": 2})


def clean_event(emit):
    emit({"ev": "startup", "platform": "tpu"})


def clean_beacon(emit):
    emit({"ev": "clock_beacon", "ts": 1.0, "step": 3})


def clean_serving_metrics(reg):
    reg.observe("itl_s", 0.01)
    reg.set_gauge("slot_occupancy", 2)


def clean_reload_metrics(reg):
    # reload/journal METRICS are fine anywhere — only raw records are
    # restricted to their owning modules
    reg.inc("reloads")
    reg.inc("journal_replayed")
    reg.observe("reload_duration_s", 1.5)


def clean_replay_instant(emit):
    # journal_replay is a plain instant, not a journal record
    emit({"ev": "journal_replay", "ts": 1.0, "resumed": 3})


def clean_router_metrics(reg):
    # router METRICS are fine anywhere — only raw route records are
    # restricted to serving/router.py
    reg.inc("handoff_resumed")
    reg.set_gauge("replicas_up", 2)
    reg.observe("latency_s", 0.2)


def clean_score_metrics(reg):
    # scoring METRICS are fine anywhere — only raw ev:"score" records
    # are restricted to progen_tpu/workloads/
    reg.inc("sequences_scored", 8)
    reg.set_gauge("goodput_pct", 91.0)


def clean_slo_metrics(reg):
    # SLO-adjacent METRICS are fine anywhere — only raw ev:"slo"
    # transition records are restricted to telemetry/slo.py
    reg.set_gauge("slo_burn_rate", 0.4)
    reg.inc("slo_transitions")


def clean_collector_usage(make_sample, sink):
    # samples/alerts built through their constructors are fine
    # anywhere — only raw dict literals are restricted
    rec = make_sample(
        ts=1.0, source="r0", role="replica", up=True, age_s=0.5
    )
    sink.staleness(source="r0", up=False, age_s=12.0)
    return rec


def clean_fleet_metrics(reg):
    # fleet-rollup METRICS are fine anywhere
    reg.set_gauge("fleet_up", 3.0)
    reg.set_gauge("replicas_live", 2.0)
    reg.inc("alerts_emitted")


def clean_prefix_cache_metrics(reg):
    # prefix-cache METRICS are fine anywhere — only raw records are
    # restricted to serving/prefix_cache.py
    reg.set_gauge("prefix_cache_hits", 3)
    reg.set_gauge("prefix_cache_bytes", 1 << 20)
    reg.inc("prefix_cache_hit_tokens", 64)


def clean_autoscale_metrics(reg):
    # autoscale/rebalance METRICS are fine anywhere — only raw
    # ev:"scale" decision records are restricted to fleet/autoscaler.py
    reg.inc("replicas_added")
    reg.set_gauge("replicas_retired", 1.0)
    reg.inc("rebalance_requested")


def clean_transport_metrics(reg):
    # transport METRICS are fine anywhere — only raw ev:"frame_drop"
    # records are restricted to fleet/transport.py
    reg.inc("frames_in", 3)
    reg.inc("accept_drops")


def clean_scale_consumer(records):
    # consuming scale records (the CI smoke, summarize) is fine — only
    # building the raw dict literal is restricted
    return [r for r in records if r.get("action") == "up"]


def clean_notify_metrics(reg):
    # delivery METRICS are fine anywhere — only raw ev:"notify"
    # records are restricted to telemetry/alert_router.py
    reg.inc("notifications_sent")
    reg.inc("notifications_silenced")


def clean_notify_consumer(records):
    # consuming notify records (console tail, CI asserts) is fine —
    # only building the raw dict literal is restricted
    return [r for r in records if r.get("status") == "sent"]


def clean_ship_metrics(reg):
    # retention METRICS are fine anywhere — only raw ev:"ship"
    # records are restricted to telemetry/tsdb.py
    reg.inc("blocks_shipped")
    reg.set_gauge("archive_bytes", 1 << 20)


def clean_deploy_consumer(records):
    # consuming deploy-ledger records (kill-matrix asserts, the CI
    # deployment smoke) is fine — only building the raw dict literal
    # is restricted to progen_tpu/deploy/
    return [r for r in records if r.get("op") == "converged"]


def clean_deploy_metrics(reg):
    # deploy-adjacent METRICS are fine anywhere — only raw ev:"deploy"
    # records are restricted to progen_tpu/deploy/
    reg.set_gauge("checkpoint_digest", 123456.0)
    reg.inc("reload_rejected")


def clean_other_ev_dict():
    # dict literals with other ev tags are not the collector's grammar
    return {"ev": "tsdb_block", "seq": 4, "level": 1}


def clean_flight_consumer(records):
    # consuming flight-dump receipts (query --trace, the forensics
    # smoke) is fine — only EMITTING the raw record is restricted to
    # telemetry/flight.py
    return [r for r in records if r.get("op") == "dumped"]


def clean_flight_metrics(reg):
    # forensics METRICS are fine anywhere — only raw ev:"flight"
    # records are restricted to telemetry/flight.py
    reg.inc("flight_dumps")


def clean_profile_consumer(records):
    # pairing requested windows with their started/stopped acks is a
    # consumer concern — only emitting the raw record is restricted
    return [r for r in records if r.get("op") in ("started", "stopped")]

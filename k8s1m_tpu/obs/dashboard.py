"""Grafana dashboard generator — the grafana-dashboard/ equivalent.

The reference ships a hand-maintained 66-panel dashboard JSON with
dedicated Scheduler / etcd / apiserver / kwok rows
(reference grafana-dashboard/dashboard.json; panels like "Scheduling
attempt rate" and "kwok_node_lease_delay_percentile max").  Hand-written
dashboards drift as metrics change, so here the dashboard is *generated*
from the metric registry: every Counter becomes a rate panel, every
Gauge a timeseries, every Histogram a p50/p99 percentile panel, grouped
into rows by subsystem prefix.

    python -m k8s1m_tpu.obs.dashboard > dashboard.json

imports the subsystems first so their metrics register, then emits a
Grafana v10 schema dashboard for a Prometheus datasource scraping
obs.http.start_metrics_server / the store server's --metrics-port.
"""

from __future__ import annotations

import json

from k8s1m_tpu.obs.metrics import (
    CallbackMetric,
    Counter,
    Gauge,
    Histogram,
    REGISTRY,
)

# Row layout mirrors the reference dashboard's subsystem rows.  The
# graftlint metrics-registry pass checks this list BOTH ways: every
# prefix must match a declared metric (no silently empty rows) and
# every declared metric must land under some prefix (no unobservable
# evidence) — keep it in sync with the obs/metrics declarations.
ROWS = [
    ("Scheduler", ("coordinator_", "leader_", "webhook_", "shardset_")),
    # Quiesce-free pipelining evidence: quiesce reasons, in-flight depth,
    # and the host-stage overlap split (pipeline_* in control/coordinator);
    # where a wave's host time goes, stage by stage (every label of
    # coordinator_cycle_seconds: the step's stages and the bind stage's
    # children bind_gather / bind_cas / bind_account / bind_per_pod /
    # bind_nofit); and the collector that pauses it all (obs/gcspan.py:
    # process_gc_* by generation).  A metric lands in the row with the
    # longest prefix that matches it.
    ("Scheduling cycle", ("pipeline_", "coordinator_cycle_", "process_gc_")),
    # Per-pod lifecycle tracing (obs/podtrace.py): the schedule-to-bind
    # latency decomposed by stage and the trace-bus accounting.
    ("Latency attribution", ("pod_stage_", "podtrace_")),
    # Cached + overlapped pod encoding (snapshot/hotfeed.py): encode
    # seconds by path, template-cache hit/miss, staged-batch use and the
    # stale-discard reasons.
    ("Host feed", ("hotfeed_",)),
    # The dp x sp sharded execution path (parallel/): mesh axis sizes,
    # sharded dirty-row scatters by column class, per-dp-shard feed depth.
    ("Mesh (dp x sp sharded cycle)", ("mesh_",)),
    # The 1,048,576-row operating shape (ISSUE 14 megarow): cold-build
    # wall seconds (bootstrap relist -> bulk ingest -> device table),
    # bulk-ingest row rate (snapshot/bulkload + bulk_upsert) with its
    # template / per-node split, and the host mirror's column-byte
    # budget under the narrow-dtype rule.
    ("Million-row (megarow)", ("megarow_", "bulkload_")),
    # Packed device snapshot + buffer donation (snapshot/packing.py,
    # ISSUE 10 devicestate): table HBM bytes by layout, per-wave commit
    # donations split by whether the runtime honored them in place, and
    # fail-closed packed-layout rebuilds by overflow reason.
    ("Device memory", ("device_", "commit_donation_")),
    ("Overload control", ("loadshed_", "admission_", "breaker_",
                          "degraded_")),
    # Multi-tenant fairness (k8s1m_tpu/tenancy): per-class admitted
    # throughput and debt, preemption evictions, gang all-or-none
    # settlement outcomes.
    ("Multi-tenant fairness", ("tenant_", "preemption_", "gang_")),
    # Coordinator failover (control/leader.py): takeover counts and
    # recovery seconds by warm/cold mode, lease-epoch fence rejections
    # by write path, the standby mirror's watch lag, and reconcile
    # repairs at takeover.
    ("Failover", ("failover_", "fencing_", "standby_")),
    # Fault injection + the one shared RetryPolicy (k8s1m_tpu/faultline).
    ("Resilience (faultline)", ("faultline_", "retry_")),
    ("Store (mem-etcd)", ("memstore_",)),
    # The apiserver-tier fan-out under storm (ISSUE 15 watchplane):
    # upstream breaks split into diff-replay resumes vs cancel-everyone
    # invalidations, per-subscriber latest-only coalescing volume, and
    # the live count of lag-degraded watchers.
    ("Watch fanout (watchplane)", ("watchcache_",)),
    ("KWOK nodes", ("kwok_", "kubelet_")),
]

_PANEL_W = 8
_PANEL_H = 7


def _target(expr: str, legend: str = "") -> dict:
    return {"expr": expr, "legendFormat": legend or "{{instance}}"}


def _panel(pid: int, title: str, targets: list[dict], x: int, y: int) -> dict:
    return {
        "id": pid,
        "title": title,
        "type": "timeseries",
        "datasource": {"type": "prometheus", "uid": "${datasource}"},
        "gridPos": {"h": _PANEL_H, "w": _PANEL_W, "x": x, "y": y},
        "fieldConfig": {"defaults": {"unit": "short"}, "overrides": []},
        "targets": targets,
    }


def _panels_for(metric) -> list[tuple[str, list[dict]]]:
    name = metric.name
    labels = "by (%s) " % ", ".join(metric.labelnames) if metric.labelnames else ""
    if isinstance(metric, Counter):
        return [(
            f"{name} rate",
            [_target(f"sum {labels}(rate({name}[1m]))",
                     "-".join("{{%s}}" % l for l in metric.labelnames))],
        )]
    if isinstance(metric, Histogram):
        panels = [(
            f"{name} p50/p99",
            [
                _target(
                    f"histogram_quantile(0.5, sum by (le) (rate({name}_bucket[1m])))",
                    "p50",
                ),
                _target(
                    f"histogram_quantile(0.99, sum by (le) (rate({name}_bucket[1m])))",
                    "p99",
                ),
            ],
        )]
        if metric.labelnames:
            # Seconds spent a second, one series a label set: which stage
            # of the cycle the time goes to, not only how long one takes.
            panels.append((
                f"{name} seconds/s by {', '.join(metric.labelnames)}",
                [_target(f"sum {labels}(rate({name}_sum[1m]))",
                         "-".join("{{%s}}" % l for l in metric.labelnames))],
            ))
        return panels
    if isinstance(metric, Gauge):
        return [(
            name,
            [_target(f"sum {labels}({name})",
                     "-".join("{{%s}}" % l for l in metric.labelnames))],
        )]
    if isinstance(metric, CallbackMetric):
        # Scrape-computed sample sets (e.g. the store's lock-contention
        # cells, labeled by method/structure/rw, reference
        # "mem_etcd_lock_count" panels).
        if metric.kind == "counter":
            return [(f"{name} rate", [_target(f"rate({name}[1m])")])]
        return [(name, [_target(name)])]
    return []


def build_dashboard(registry=None) -> dict:
    registry = registry or REGISTRY
    panels = []
    pid = 1
    y = 0
    def row_of(name: str) -> str | None:
        """The row whose prefix matches ``name`` longest."""
        best = max(
            ((len(p), title) for title, prefixes in ROWS for p in prefixes
             if name.startswith(p)),
            default=(0, None),
        )
        return best[1]

    for row_title, _prefixes in ROWS:
        row_metrics = [
            m for m in registry.metrics() if row_of(m.name) == row_title
        ]
        if not row_metrics:
            continue
        panels.append({
            "id": pid, "type": "row", "title": row_title,
            "collapsed": False,
            "gridPos": {"h": 1, "w": 24, "x": 0, "y": y},
        })
        pid += 1
        y += 1
        x = 0
        for m in sorted(row_metrics, key=lambda m: m.name):
            for title, targets in _panels_for(m):
                panels.append(_panel(pid, title, targets, x, y))
                pid += 1
                x += _PANEL_W
                if x >= 24:
                    x = 0
                    y += _PANEL_H
        if x:
            y += _PANEL_H
    return {
        "title": "k8s1m-tpu",
        "uid": "k8s1m-tpu",
        "schemaVersion": 39,
        "refresh": "10s",
        "time": {"from": "now-30m", "to": "now"},
        "templating": {
            "list": [{
                "name": "datasource", "type": "datasource",
                "query": "prometheus",
            }]
        },
        "panels": panels,
    }


def main() -> None:
    # Import the subsystems for their metric registrations — the
    # dashboard covers whatever the code actually exports.
    import k8s1m_tpu.cluster.kwok_controller  # noqa: F401
    import k8s1m_tpu.control.coordinator  # noqa: F401
    import k8s1m_tpu.control.leader  # noqa: F401
    import k8s1m_tpu.control.webhook  # noqa: F401
    import k8s1m_tpu.loadshed  # noqa: F401
    import k8s1m_tpu.store.etcd_server  # noqa: F401
    import k8s1m_tpu.store.watch_cache  # noqa: F401
    import k8s1m_tpu.tenancy  # noqa: F401

    print(json.dumps(build_dashboard(), indent=1))


if __name__ == "__main__":
    main()

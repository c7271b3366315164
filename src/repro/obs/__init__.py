"""Observability plane: the live half (DESIGN.md §13 — MetricsHub
counters/probes, the ``subscribe_stats`` stream, anomaly-driven fleet
defense) and the post-mortem half (§14 — durable snapshot/trace
retention, workunit lifecycle tracing, windowed drift defense).

The exports load on first use, so a leaf such as ``repro.obs.spans``
(which the core layers import) pulls in none of the operator plane."""
import importlib

_EXPORTS = {
    "repro.obs.anomaly": ["KILL", "PAGE", "QUARANTINE", "RELEASE",
                          "SCHEDULE_VERSION", "AnomalyEvent", "FleetDefense"],
    "repro.obs.metrics": ["STREAM_VERSION", "MetricsHub", "attach_cache",
                          "attach_coalescer", "attach_engine", "attach_grid",
                          "attach_intake"],
    "repro.obs.retention": ["OBS_STORE_DB", "OBS_STORE_NAME", "STORE_VERSION",
                            "RetentionSink", "SnapshotStore",
                            "SqliteSnapshotStore", "obs_store_path",
                            "open_snapshot_store"],
    "repro.obs.stream": ["BackgroundSubscriber", "StatsSubscriber"],
    "repro.obs.trace": ["TRACE_VERSION", "WorkUnitTracer", "wu_sampled"],
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = [
    "MetricsHub", "STREAM_VERSION", "attach_engine", "attach_grid",
    "attach_coalescer", "attach_cache", "attach_intake",
    "AnomalyEvent", "FleetDefense", "SCHEDULE_VERSION",
    "QUARANTINE", "RELEASE", "PAGE", "KILL",
    "StatsSubscriber", "BackgroundSubscriber",
    "SnapshotStore", "SqliteSnapshotStore", "RetentionSink",
    "open_snapshot_store", "obs_store_path", "STORE_VERSION",
    "OBS_STORE_NAME", "OBS_STORE_DB",
    "WorkUnitTracer", "wu_sampled", "TRACE_VERSION",
]


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))

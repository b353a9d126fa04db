"""repro.core — the paper's contribution: call-stack profiling as a first-class
framework feature (host plane + device plane + anomaly detection).

Exports resolve lazily (PEP 562): the profiling plane (``calltree`` /
``sampler`` / ``detector`` / ``report``) is pure-Python and must stay
importable in milliseconds — the out-of-process ``repro.profilerd`` daemon
imports it on every attach — while the device plane (``engines`` /
``hlo_tree`` / ``roofline``) pulls in JAX and is only paid for on first use.
"""

from importlib import import_module

_EXPORTS = {
    # host plane (light, no jax)
    "SAMPLES": ".calltree",
    "CallNode": ".calltree",
    "CallTree": ".calltree",
    "AnomalyEvent": ".detector",
    "DominanceDetector": ".detector",
    "LIVELOCK_CLEARED": ".detector",
    "Rule": ".detector",
    "StragglerDetector": ".detector",
    "TrendDetector": ".detector",
    "TrendRule": ".detector",
    "TrendVerdict": ".detector",
    "WatchdogLoop": ".detector",
    "segment_phases": ".detector",
    "CountSealer": ".snapshot",
    "EpochMeta": ".snapshot",
    "EpochSealer": ".snapshot",
    "SnapshotError": ".snapshot",
    "TimelineReader": ".snapshot",
    "TimelineWriter": ".snapshot",
    "load_snapshot": ".snapshot",
    "save_snapshot": ".snapshot",
    "DEFAULT_PERIOD_S": ".sampler",
    "SamplerBackend": ".sampler",
    "SamplerConfig": ".sampler",
    "StackSampler": ".sampler",
    "classify_frame": ".sampler",
    "collapse_stack": ".sampler",
    "frame_symbol": ".sampler",
    "make_sampler": ".sampler",
    "ViewConfig": ".report",
    "NO_MATCH_MARKER": ".report",
    "breakdown": ".report",
    "diff_rows": ".report",
    "name_shares": ".report",
    "render_diff": ".report",
    "render_html": ".report",
    "save_views": ".report",
    "share_regressions": ".report",
    "write_report": ".report",
    "EXPORT_FORMATS": ".export",
    "build_diff_tree": ".export",
    "diff_flamegraph_html": ".export",
    "export_tree": ".export",
    "flamegraph_html": ".export",
    "from_folded": ".export",
    "to_folded": ".export",
    "to_speedscope": ".export",
    # device plane (imports jax on first access)
    "BlockwiseEngine": ".engines",
    "CompiledEngine": ".engines",
    "EagerEngine": ".engines",
    "compare_engines": ".engines",
    "COLLECTIVE_OPS": ".hlo_tree",
    "DeviceTree": ".hlo_tree",
    "build_device_tree": ".hlo_tree",
    "collective_summary": ".hlo_tree",
    "load_device_tree": ".hlo_tree",
    "parse_hlo_module": ".hlo_tree",
    "save_device_tree": ".hlo_tree",
    "tree_from_compiled": ".hlo_tree",
    "DEVICE_TREE_FILENAME": ".planes",
    "PLANES": ".planes",
    "PlaneError": ".planes",
    "annotate_tree": ".planes",
    "dominant_term": ".planes",
    "select_plane": ".planes",
    "PEAKS": ".roofline",
    "HardwareSpec": ".roofline",
    "peaks_for": ".roofline",
    "RooflineReport": ".roofline",
    "report_from_artifacts": ".roofline",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}") from None
    value = getattr(import_module(module, __name__), name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))

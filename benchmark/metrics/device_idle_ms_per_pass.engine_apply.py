"""Device-idle time per pass under the engine's `engine.apply` host
span (benchmark/xplane_ops.py)."""


def read(run):
    ops, passes = getattr(run, "trace_ops", None), run.counters.get("passes")
    if ops is None or not passes or not ops.devices:
        return None
    return ops.idle_in_span_s.get("engine.apply", 0.0) / passes * 1e3

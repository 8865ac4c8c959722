"""Fused-kernel re-dispatches per pass: `engine.redo` spans in the
window (benchmark/xplane_ops.py), work the kernel did twice."""


def read(run):
    ops, passes = getattr(run, "trace_ops", None), run.counters.get("passes")
    if ops is None or not passes:
        return None
    return ops.redo_spans / passes

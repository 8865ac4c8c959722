"""Device time of the fused kernel's `round_received` stage per pass: leaf-op
time under its jax.named_scope (benchmark/xplane_ops.py)."""


def read(run):
    ops, passes = getattr(run, "trace_ops", None), run.counters.get("passes")
    if ops is None or not passes or "round_received" not in ops.fused_stages_s:
        return None
    return ops.fused_stages_s["round_received"] / passes * 1e3

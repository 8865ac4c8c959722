"""The round-received sweep's share of its HBM roofline: the bytes it
must read over the window (run.counters["sweep_bytes"], summed per pass
by benchmark/sweep_bytes.py) over its stage time
(`fused_stage_ms_per_pass.round_received`'s leaf-op time, per device
plane) times the chip's peak bytes per second (benchmark/peaks.json)
times the devices."""

from benchmark.peaks import peaks


def read(run):
    ops = getattr(run, "trace_ops", None)
    swept, devices = (run.counters.get(k) for k in ("sweep_bytes", "devices"))
    if ops is None or not swept or not devices:
        return None
    t = ops.fused_stages_s.get("round_received")
    if not t:
        return None
    bw = peaks(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * swept / (t * devices * bw)

"""Node ingest: host wall per pass of mirroring the pass into the Store
and committing its blocks (Core.phase_ns "mirror", the `hg.apply` span
of TpuHashgraph), over the window."""


def read(run):
    passes, mirror = run.counters.get("passes"), run.counters.get("mirror_ns")
    if not passes or not mirror:
        return None
    return mirror / passes / 1e6

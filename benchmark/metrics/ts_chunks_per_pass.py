"""Chunks of the fused kernel's timestamp loop per pass, from the
engine's per-pass counts (IncrementalEngine.pass_counts, summed by the
mesh engine driver): ceil(received / chunk) in a pass that receives."""


def read(run):
    passes, chunks = run.counters.get("passes"), run.counters.get("ts_chunks")
    if not passes or chunks is None:
        return None
    return chunks / passes

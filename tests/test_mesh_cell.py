"""The benchmark's four-chip cell, v4096-mesh-replay, at a tiny size on
four of the CPU's virtual devices: its sharded reference against the
plain one, and its driver and readers end to end through the harness."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmark import reference, reference_sharded
from benchmark.gossip import random_gossip

CELL = "v4096-mesh-replay"


@pytest.mark.parametrize(
    "n,e,seed,sm", [(16, 3000, 5, None), (8, 1500, 2, None), (16, 3000, 5, 10)],
    ids=["n16", "n8", "n16-sm-short"])
def test_sharded_reference_equals_reference(n, e, seed, sm):
    """The same consensus, to the bit, with la split by column and LA by
    chain over four devices and every step in blocks (the last case is
    the control's lowered supermajority)."""
    d = random_gossip(n, e, seed)
    ts = np.arange(e) * 7 % 1000  # repeated timestamps: ranks must tie
    args = (n, d.sp, d.op, d.creator, d.index, d.coin, ts)
    want = reference.consensus(*args, sm=sm)
    got = reference_sharded.consensus(*args, devices=jax.devices()[:4], sm=sm)
    assert (want.rr >= 0).sum() > e // 2
    for name in ("rounds", "witness", "famous", "rr", "cts"):
        a, b = getattr(want, name), getattr(got, name)
        assert a.shape == b.shape and (a == b).all(), name


def test_mesh_cell_runs_through_the_harness(monkeypatch):
    """The cell's driver on a 16-validator job over four devices, traced,
    its round-received sweep forced to chunks of 64 lanes: correct
    against the sharded reference, its chunk counts read by the cell's
    readers, the op-metadata readers silent without a kept trace."""
    from babble_tpu.ops import incremental as inc
    from benchmark import run as harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", harness.CACHE_DIR)
    monkeypatch.setattr(inc, "RR_PLANE_BYTES", 64 * 16)
    config = dict(harness.load_json(
        f"{harness.HERE}/configs/validators4096-mesh.json"), validators=16)
    mix = {"loop": "closed", "clients": 1, "job_events": 1600, "sync": 200,
           "dag_seed": 1}
    run = harness.start(CELL, 5, 0.5, True, config=config, mix=mix,
                        need_chip=False)
    assert run.correct, run.checks
    c = run.counters
    assert c["devices"] == 4 and c["jobs"] >= 1
    assert c["passes"] >= 8 * c["jobs"]
    assert c["rr_chunks"] > c["passes"]  # some pass swept several chunks
    assert c["ts_chunks"] >= 1 and c["sweep_bytes"] > 0
    assert run.e2e["ordered_events_per_s"] > 0
    m = run.metrics
    assert m["rr_chunks_per_pass"]["value"] == c["rr_chunks"] / c["passes"]
    assert m["ts_chunks_per_pass"]["value"] == c["ts_chunks"] / c["passes"]
    assert m["compiles_in_window"]["value"] == 0
    for name in ("collective_ms_per_pass", "roofline_share.round_received"):
        assert harness.metric_reader(name)(run) is None
    line = harness.result_line(run)
    assert line["device"]["count"] == 4


def test_new_readers_are_silent_on_a_run_without_their_counts():
    """On a run of a program that lacks what they read (the parent's), the
    readers return nothing and do not raise."""
    from benchmark import run as harness

    run = SimpleNamespace(counters={"passes": 10}, trace_ops=None,
                          trace_summary=None, devices=[])
    for name in ("rr_chunks_per_pass", "ts_chunks_per_pass",
                 "collective_ms_per_pass", "roofline_share.round_received"):
        assert harness.metric_reader(name)(run) is None


def test_collective_kinds_and_sweep_bytes():
    from benchmark.collectives import kind_of
    from benchmark.sweep_bytes import sweep_bytes

    assert kind_of("all-gather-start.3") == "all-gather"
    assert kind_of("all-reduce.12") == "all-reduce"
    assert kind_of("all-reduce-scatter.1") == "reduce-scatter"
    assert kind_of("collective-permute-done") == "collective-permute"
    assert kind_of("all-to-all.2") == "all-to-all"
    assert kind_of("fusion.7") == "" and kind_of("") == ""
    # 32 rounds over a 1M-lane window at n=4096 on four chips.
    assert sweep_bytes(1 << 20, 4096, 32, 4) == 32 * (
        4 * 4096 * 4096 * 4 + 16 * (1 << 20))

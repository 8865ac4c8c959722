"""Multi-chip parity: IncrementalEngine with its carries sharded over
meshes of the 8 virtual CPU devices (conftest.py) — as
Core(engine_mesh=...) runs it — must match the plain reference
(benchmark/reference.py) and the single-device engine bit-for-bit:
rounds, witnesses, witness table, fame, round received, and consensus
timestamps (__graft_entry__.dryrun_multichip checks the same)."""

from __future__ import annotations

import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from babble_tpu.ops.dag import synthetic_dag

from fixtures import assert_engine_matches_reference, feed_engine


def _mesh(shape):
    devices = jax.devices()
    assert len(devices) >= 8, "conftest must provision the virtual mesh"
    if shape == "1d":
        return Mesh(np.array(devices[:8]), ("sp",)), "sp"
    if shape == "sp4":
        # The four-chip cell's mesh, built as a node builds it.
        from babble_tpu.node.core import engine_mesh

        return engine_mesh(devices, 16, 4), "sp"
    # Hosts x chips: shards span both axes — the multi-host layout
    # where XLA routes intra-host collective segments over ICI and
    # cross-host segments over DCN (the reference's TCP backend spans
    # hosts the same way).
    return Mesh(np.array(devices[:8]).reshape(2, 4), ("dcn", "ici")), (
        "dcn", "ici")


@pytest.mark.parametrize(
    "n,e,shape",
    [(8, 400, "1d"), (16, 1000, "1d"), (64, 5000, "1d"), (8, 480, "2d"),
     (64, 5000, "2d"), (16, 1000, "sp4")],
    ids=["n8", "n16", "n64-e5000", "n8-dcn-ici", "n64-dcn-ici", "sp4"],
)
def test_mesh_engine_matches_reference(n, e, shape):
    from babble_tpu.ops.incremental import IncrementalEngine

    mesh, axis = _mesh(shape)
    dag, _ = synthetic_dag(n, e, seed=11)
    eng = IncrementalEngine(n, capacity=256, block=64, k_capacity=8,
                            mesh=mesh, mesh_axis=axis)
    assert eng.devices == mesh.devices.size
    feed_engine(eng, dag, max(e // 5, 97))
    assert_engine_matches_reference(eng, dag)


@pytest.mark.parametrize("shape", ["1d", "2d"], ids=["1d", "dcn-ici"])
def test_sharded_incremental_engine(shape):
    """IncrementalEngine with mesh-resident carries (GSPMD-partitioned
    kernels) must match the single-device engine bit-for-bit across
    batched ingest, capacity growth, and chain-bucket growth — and the
    resident carries must be PHYSICALLY partitioned (the memory-scaling
    claim: a node's DAG capacity grows with its chips)."""
    from babble_tpu.ops.incremental import IncrementalEngine

    mesh, axis = _mesh(shape)
    n, e, bs = 16, 1200, 131
    dag, _ = synthetic_dag(n, e, seed=5)

    ref = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8,
                            mesh=mesh, mesh_axis=axis)
    # The mesh engine must select the non-donating kernel twins: under
    # GSPMD the donated growth-concat inputs are frequently unusable
    # (resharded outputs), and XLA would warn on every capacity step.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        k = 0
        while k < e:
            hi = min(k + bs, e)
            for g in (ref, eng):
                g.append_batch(
                    dag.self_parent[k:hi], dag.other_parent[k:hi],
                    dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                    np.arange(k, hi))
                g.run()
            k = hi
    donation = [w for w in caught if "donated buffers" in str(w.message)]
    assert not donation, f"XLA donation warnings: {donation[:3]}"

    assert (eng.rounds[:e] == ref.rounds[:e]).all()
    assert (eng.witness[:e] == ref.witness[:e]).all()
    assert (eng.rr[:e] == ref.rr[:e]).all()
    assert (eng.cts_ns[:e] == ref.cts_ns[:e]).all()
    assert (eng.famous == ref.famous).all()
    assert eng.undecided_rounds == ref.undecided_rounds

    # The big carries must be physically partitioned across the mesh.
    d = 8
    for name in ("_la", "_chain_la", "_ranks"):
        arr = getattr(eng, name)
        shards = arr.addressable_shards
        total = int(np.prod(arr.shape))
        per_dev = sorted(int(np.prod(s.data.shape)) for s in shards)
        assert len(per_dev) == d, name
        # Uneven event-axis splits leave the last shard smaller; no
        # shard may hold the whole (replicated) table.
        assert per_dev[-1] < total, f"{name} is replicated, not sharded"
        assert sum(per_dev) == total, name


def test_node_engine_mesh_gossip():
    """A live 4-node testnet whose tpu engines keep their carries
    sharded over a 4-device mesh (Config.engine_mesh / --engine_mesh):
    gossip must converge exactly as with the single-device engine."""
    from test_node import check_gossip, make_nodes, run_gossip

    nodes = make_nodes(4, "inmem", engine="tpu", engine_mesh=4)
    for node in nodes:
        eng = node.core.hg.engine
        assert eng._mesh is not None
        assert len(eng._la.sharding.device_set) == 4
    run_gossip(nodes, target_round=3, timeout=300.0)
    check_gossip(nodes)


def test_engine_mesh_shrinks_to_a_divisor(caplog):
    """The mesh a node builds for its engine (Core, chip_smoke and the
    benchmark's mesh driver share it): the first d devices on `sp`, d
    shrunk to the largest divisor of the validator count."""
    from babble_tpu.node.core import engine_mesh

    devs = jax.devices()
    assert engine_mesh(devs, 16, 0) is None
    assert engine_mesh(devs, 16, 1) is None
    mesh = engine_mesh(devs, 4096, 4)
    assert mesh.axis_names == ("sp",)
    assert list(mesh.devices.flat) == devs[:4]
    with caplog.at_level("WARNING", logger="babble_tpu"):
        assert engine_mesh(devs, 12, 8).devices.size == 6
        assert engine_mesh(devs, 7, 4) is None
    assert sum("does not divide" in r.getMessage()
               for r in caplog.records) == 2
    with pytest.raises(ValueError, match="only 2 devices"):
        engine_mesh(devs[:2], 16, 4)


def _feed(eng, dag, syncs):
    """Append and run the DAG in syncs of the given sizes; the pass
    counts of each pass."""
    counts, k = [], 0
    for size in syncs:
        hi = min(k + size, dag.e)
        eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                         dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                         np.arange(k, hi))
        eng.run()
        counts.append(dict(eng.pass_counts))
        k = hi
    eng.close()
    return counts


@pytest.mark.parametrize(
    "where,syncs",
    [("mesh4", [150] * 10), ("mesh4", [1200, 150, 150]),
     ("one_device", [1200, 150, 150])],
    ids=["mesh4-steady", "mesh4-backlog", "one-device-backlog"])
def test_round_received_sweep_in_chunks(monkeypatch, where, syncs):
    """The round-received sweep walked in chunks of 64 lanes (forced
    through RR_PLANE_BYTES), on a 4-device mesh with each chunk's lanes
    split over the devices or on one device, equals the one-chunk
    engine and the plain reference bit for bit: rounds, witness, fame,
    round received and consensus timestamps. The backlog cases' first
    sync is deep enough that its first decisions release hundreds of
    events in one pass."""
    from babble_tpu.node.core import engine_mesh
    from babble_tpu.ops import incremental as inc
    from babble_tpu.ops.incremental import IncrementalEngine
    from benchmark import reference

    n = 16
    dag, _ = synthetic_dag(n, sum(syncs), seed=7)
    e = dag.e
    sizes = dict(capacity=256, block=64, k_capacity=8)
    one = IncrementalEngine(n, **sizes)
    whole = _feed(one, dag, syncs)
    assert all(c["rr_chunks"] == (c["n_und"] > 0) for c in whole)

    mesh = engine_mesh(jax.devices(), n, 4) if where == "mesh4" else None
    monkeypatch.setattr(inc, "RR_PLANE_BYTES", 64 * 4 * n // (4 if mesh else 1))
    eng = IncrementalEngine(n, mesh=mesh, **sizes)
    assert eng.devices == (4 if mesh else 1)
    counts = _feed(eng, dag, syncs)
    assert max(c["rr_chunks"] for c in counts) >= 3
    for c in counts:
        assert c["au"] > 64
        assert c["rr_chunks"] == -(-c["n_und"] // 64)

    for name in ("rounds", "witness", "rr", "cts_ns"):
        assert (getattr(eng, name)[:e] == getattr(one, name)[:e]).all(), name
    assert (eng.famous == one.famous).all()
    ref = reference.consensus(n, dag.self_parent[:e], dag.other_parent[:e],
                              dag.creator[:e], dag.index[:e], dag.coin[:e],
                              np.arange(e))
    got = ref.rr >= 0
    assert got.sum() > e // 4
    assert (eng.rounds[:e] == ref.rounds).all()
    assert (eng.witness[:e] == ref.witness).all()
    assert (eng.rr[:e] == ref.rr).all()
    assert (eng.cts_ns[:e][got] == ref.cts[got]).all()
    r = len(eng.famous)
    assert (eng.famous == ref.famous[:r]).all()

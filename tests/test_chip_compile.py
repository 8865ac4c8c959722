"""The main path's kernels, compiled for a described TPU v5e here,
without the chip: what the chip's compiler refuses (a program that
does not fit HBM, a kernel it cannot partition over the mesh) fails
here at no chip time.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and xdist workers import every test
file (see /opt/skills/guides/on-chip-measurement, section 2). Nothing
runs, so these say nothing about results or times."""

from __future__ import annotations

import numpy as np
import pytest

HBM_BYTES = 16 * 10**9  # one v5e chip

N = 1024  # the north-star validator count
N_MESH = 4096  # BASELINE.json config 5, on four chips
CAP = 65536  # node presize (node/core.py engine_sizes)
K = 64  # engine_sizes' chain bucket at n=1024 and n=4096
BP = 4096  # one 4096-event sync
M = 16  # new events per creator in a sync: the x4 bucket floor
# The v4096-mesh-replay job's largest shapes: capacity and chain bucket
# grown to hold 88 events per validator, 16,384-event syncs, and the
# 1M-lane bucket a window past 262,144 undecided events lands in (the
# job's own window peaks just under that on a v5e 2x2 host).
BACKLOG = {"cap": 1 << 19, "k": 128, "lanes": 1 << 20, "bp": 16384}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("sp",))


@pytest.fixture
def tpu_branches(monkeypatch):
    """Trace as the engine traces on a TPU: the 4x broadcast budget.
    (The table fold's MXU form is picked by calling it below.)"""
    from babble_tpu.ops import kernels

    monkeypatch.setattr(kernels, "_bcast_budget", lambda: 1 << 28)


def fits(compiled, devices: int = 1) -> int:
    """Bytes one device needs for `compiled`; asserts they fit."""
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < need < HBM_BYTES, (need, devices)
    return need


def pass_kernels(n, *, cols, chains, vec, cap=CAP, k=K, lanes=4 * BP,
                 bp=BP):
    """(name, jitted kernel, args, static kwargs) for each dispatch of
    one engine pass (ops/incremental.py _stage_pass): ingest, chain
    ingest, closure, fd-rank fold, fused consensus epilogue. `cols`,
    `chains` and `vec` shard the [cap, n] carries, the chain-axis
    carries and everything else; on a mesh the round-received sweep's
    lanes split as the chain axis does. `lanes` is the undecided window
    the sweep walks, `bp` the sync's batch bucket."""
    import jax
    import jax.numpy as jnp

    from babble_tpu.ops import incremental as inc

    i32, i8 = jnp.int32, jnp.int8

    def s(shape, dtype=i32, sh=vec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    c1 = cap + 1
    scalar = s((), i32)
    ev = [s((c1,)) for _ in range(4)]  # sp, op, creator, index
    coin, rb0 = s((c1,), i8), s((c1,))
    la, rb = s((c1, n), sh=cols), s((c1,))
    chain = [s((n, k), sh=chains) for _ in range(3)]  # ids, ts hi, lo
    new = [s((n, M)) for _ in range(4)]  # newtab, newpos, newhi, newlo
    ranks = s((n, n, k), sh=chains)
    chain_la, chain_rb = s((n, k, n), sh=chains), s((n, k), sh=chains)
    mesh = cols is not vec
    rcap, w = 64, 32  # engine floors at n >= 1024 (_stage_pass)
    fused_args = (
        chain_la, chain_rb, s((n,)), s((cap, n), sh=cols), ranks,
        s((cap,)), chain[0], s((rcap, n)), s((rcap, n)), s((n,)), s((n,)),
        scalar, scalar, ev[0], ev[2], ev[3], coin, scalar, scalar,
        s((cap,)), s((cap,)), s((rcap, n)), s((rcap,), jnp.bool_),
        chain[1], chain[2], scalar, scalar, s((lanes,)), scalar, scalar)
    sm = 2 * n // 3 + 1
    devices = len(chains.device_set)
    return [
        ("ingest", inc._ingest_nd if mesh else inc._ingest,
         (*ev, coin, rb0, *(s((bp,)) for _ in range(4)), s((bp,), i8),
          s((bp,)), scalar), {"bp": bp}),
        ("chain_ingest", inc._chain_ingest_nd if mesh else inc._chain_ingest,
         (*chain, *new), {"n": n, "m": M}),
        ("closure", inc._closure_update_nd if mesh else inc._closure_update,
         (la, rb, *ev, rb0, scalar, scalar), {"n": n, "block": 512}),
        ("tables", inc._tables_update_nd if mesh else inc._tables_update,
         (ranks, chain_la, chain_rb, la, rb, new[0], new[1]),
         {"n": n, "m": M}),
        ("fused", inc._consensus_fused, fused_args,
         {"n": n, "sm": sm, "rcap": rcap, "bp": bp, "rw": w, "iw": w,
          "cb": 1024, "tc": 1024, "tw": 16,
          "rl": inc._rr_lanes(n, devices), "lanes": chains if mesh else None}),
    ]


@pytest.mark.parametrize(
    "kernel", ["ingest", "chain_ingest", "closure", "tables", "fused"])
def test_engine_pass_compiles_for_one_chip(one_chip, tpu_branches, kernel):
    """A steady pass of the presized n=1024 engine, TPU branches taken."""
    (_, fn, args, kw), = [k for k in pass_kernels(
        N, cols=one_chip, chains=one_chip, vec=one_chip) if k[0] == kernel]
    fits(fn.lower(*args, **kw).compile())


def mesh_kernel(mesh, kernel, **shapes):
    from jax.sharding import NamedSharding, PartitionSpec as P

    (_, fn, args, kw), = [k for k in pass_kernels(
        N_MESH, cols=NamedSharding(mesh, P(None, "sp")),
        chains=NamedSharding(mesh, P("sp")),
        vec=NamedSharding(mesh, P()), **shapes) if k[0] == kernel]
    return fn.lower(*args, **kw).compile()


@pytest.mark.parametrize(
    "kernel", ["ingest", "chain_ingest", "closure", "tables", "fused",
               "ingest-backlog", "chain_ingest-backlog", "closure-backlog",
               "tables-backlog"])
def test_engine_pass_compiles_on_four_chip_mesh(mesh, tpu_branches, kernel):
    """A pass of the mesh-resident engine at n=4096 on v5e:2x2, steady
    (node presize) or at the v4096-mesh-replay job's largest shapes
    (`-backlog`): participant columns and chain axes sharded as
    ops/incremental.py places them; every device's share fits its HBM."""
    kernel, _, backlog = kernel.partition("-")
    k = BACKLOG["k"] if backlog else K
    need = fits(mesh_kernel(mesh, kernel, **(BACKLOG if backlog else {})),
                devices=4)
    # Unsharded, the rank cube and the chain tables alone are 8 GiB
    # (16 GiB at the backlog's chain bucket).
    assert need < 2 * 4 * N_MESH * k * N_MESH


def test_p256_ladder_compiles_at_512_lanes(one_chip):
    import jax
    import jax.numpy as jnp

    from babble_tpu.ops import p256

    lanes = p256._LADDER[-1]
    assert lanes == 512

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = p256._build_kernel().lower(
        s((lanes, p256._NIBBLES)), s((lanes, p256._NIBBLES)),
        s((lanes, 16, 2, p256._LIMBS))).compile()
    fits(compiled)


def test_mesh_epilogue_fits_the_first_decision_backlog(mesh, tpu_branches):
    """Random gossip at n=4096 decides nothing before about 65 events per
    validator, so the undecided window can pass 262144 lanes and land in
    the 1M-lane bucket. Swept whole, its [4096, 1048576] int32 compare
    plane sat on every device (18.04 GB of 15.75; the chip refused it
    too). The sweep walks it in four chunks of 262,144 lanes, each split
    over the four devices: one [4096, 65536] plane per device, and no
    device holds a 4 GiB plane."""
    from babble_tpu.ops import incremental as inc

    assert inc._rr_lanes(N_MESH, 4) == BACKLOG["lanes"] // 4
    compiled = mesh_kernel(mesh, "fused", **BACKLOG)
    fits(compiled, devices=4)
    text = compiled.as_text()
    assert "[4096,65536]" in text
    assert "[4096,1048576]" not in text and "[4096,262144]" not in text

"""The main path's kernels, compiled for a described TPU v5e here,
without the chip: what the chip's compiler refuses (a program that
does not fit HBM, a Pallas block it cannot tile, a kernel it cannot
partition over the mesh) fails here at no chip time.

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


def pass_kernels(n, *, cols, chains, vec, cap=CAP, k=K, lanes=4 * BP):
    """(name, jitted kernel, args, static kwargs) for each dispatch of
    one engine pass (ops/incremental.py _stage_pass): ingest, chain
    ingest, closure, fd-rank fold, fused consensus epilogue. `cols`,
    `chains` and `vec` shard the [cap, n] carries, the chain-axis
    carries and everything else. `lanes` is the undecided window the
    round-received sweep walks."""
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
    return [
        ("ingest", inc._ingest_nd if mesh else inc._ingest,
         (*ev, coin, rb0, *(s((BP,)) for _ in range(4)), s((BP,), i8),
          s((BP,)), scalar), {"bp": BP}),
        ("chain_ingest", inc._chain_ingest_nd if mesh else inc._chain_ingest,
         (*chain, *new), {"n": n, "m": M}),
        ("closure", inc._closure_update_nd if mesh else inc._closure_update,
         (la, rb, *ev, rb0, scalar, scalar), {"n": n, "block": 512}),
        ("tables", inc._tables_update_nd if mesh else inc._tables_update,
         (ranks, chain_la, chain_rb, la, rb, new[0], new[1]),
         {"n": n, "m": M}),
        ("fused", inc._consensus_fused, fused_args,
         {"n": n, "sm": sm, "rcap": rcap, "bp": BP, "rw": w, "iw": w,
          "cb": 1024, "tc": 1024, "tw": 16}),
    ]


@pytest.mark.parametrize(
    "kernel", ["ingest", "chain_ingest", "closure", "tables", "fused"])
def test_engine_pass_compiles_for_one_chip(one_chip, tpu_branches, kernel):
    """A steady pass of the presized n=1024 engine, TPU branches taken."""
    (_, fn, args, kw), = [k for k in pass_kernels(
        N, cols=one_chip, chains=one_chip, vec=one_chip) if k[0] == kernel]
    fits(fn.lower(*args, **kw).compile())


@pytest.mark.parametrize(
    "kernel", ["ingest", "chain_ingest", "closure", "tables", "fused"])
def test_engine_pass_compiles_on_four_chip_mesh(mesh, tpu_branches, kernel):
    """A steady pass of the mesh-resident engine at n=4096 on v5e:2x2:
    participant columns and chain axes sharded as ops/incremental.py
    places them; every device's share fits its HBM."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    (_, fn, args, kw), = [k for k in pass_kernels(
        N_MESH, cols=NamedSharding(mesh, P(None, "sp")),
        chains=NamedSharding(mesh, P("sp")),
        vec=NamedSharding(mesh, P())) if k[0] == kernel]
    compiled = fn.lower(*args, **kw).compile()
    need = fits(compiled, devices=4)
    # Unsharded, the rank cube and the chain tables alone are 8 GiB.
    assert need < 2 * 4 * N_MESH * K * N_MESH


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


def test_pallas_strongly_see_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from babble_tpu.ops.pallas_kernels import strongly_see_counts

    x = jax.ShapeDtypeStruct((N, N), jnp.int32, sharding=one_chip)
    compiled = strongly_see_counts.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    fits(compiled)


def test_mesh_epilogue_refuses_the_first_decision_backlog(mesh, tpu_branches):
    """A known refusal, asserted so that it stays in view. Random gossip
    at n=4096 decides nothing before about 65 events per validator, so
    the undecided window passes 262144 lanes and lands in the 1M-lane
    bucket. The fused epilogue's round-received sweep then gathers la
    columns for every lane, [1048576, 4096] int32, replicated on each
    device: 18.04 GB of 15.75. The v5e:2x2 chip refused it the same way
    (PR 21). When that sweep is sharded or chunked, this compile fits:
    turn the test into a `fits` check."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    (_, fn, args, kw), = [k for k in pass_kernels(
        N_MESH, cols=NamedSharding(mesh, P(None, "sp")),
        chains=NamedSharding(mesh, P("sp")), vec=NamedSharding(mesh, P()),
        cap=1 << 19, k=128, lanes=1 << 20) if k[0] == "fused"]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED.*hbm"):
        fn.lower(*args, **kw).compile()

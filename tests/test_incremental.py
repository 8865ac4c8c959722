"""Incremental device engine + TpuHashgraph integration tests.

Three layers of parity, mirroring the reference's oracle strategy
(hashgraph_test.go fixtures -> core_test.go playbooks -> node_test.go
checkGossip):

1. IncrementalEngine fed in batches must equal the plain reference
   (benchmark/reference.py) bit-for-bit (rounds, witnesses, fame,
   round-received, consensus timestamps) across capacity/chain-bucket
   growth.
2. TpuHashgraph driven event-by-event must equal the incremental host
   engine on the reference fixture graphs: same rounds, witness sets,
   fame trileans, consensus order, and block hashes.
3. The live gossip runtime (reference node_test.go:396-420) must
   converge with the device engine deciding consensus.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from babble_tpu.common import StoreError
from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.event import Event
from babble_tpu.hashgraph.root import Root
from babble_tpu.hashgraph.round_info import Trilean
from babble_tpu.hashgraph.tpu_graph import TpuHashgraph
from babble_tpu.ops import incremental as inc
from babble_tpu.ops import kernels
from babble_tpu.ops.dag import synthetic_dag
from babble_tpu.ops.incremental import IncrementalEngine
from babble_tpu.ops.sim import simulate_views
from babble_tpu.telemetry import SpanRing
from benchmark import reference

from fixtures import (
    assert_engine_matches_reference,
    build_consensus_graph,
    build_funky_graph,
    build_round_graph,
    feed_engine,
    reference_of,
    reference_witness_table,
)
from test_node import check_gossip, make_nodes, run_gossip

CACHE = 10000


def make_tpu_twin(build):
    """Host graph with consensus run + a TpuHashgraph fed the same
    fixture stream (consensus run once at the end)."""
    h, b = build()
    h.divide_rounds()
    h.decide_fame()
    h.find_order()
    participants = b.participants()
    t = TpuHashgraph(participants, InmemStore(participants, CACHE),
                     capacity=64, block=64)
    for ev in b.ordered_events:
        t.insert_event(ev, True)
    t.run_consensus()
    return h, b, t


def _silent_dag(n, steps, seed):
    """A DAG in which the last n - supermajority validators are silent:
    they never sync, so only their first events exist, and nobody
    builds on them (the missing-node case, reference
    node_test.go:409-420)."""
    silent = np.arange(n) >= 2 * n // 3 + 1
    dag, _, _ = simulate_views(n, steps, silent=silent, seed=seed)
    return dag


ENGINE_CASES = {
    # (dag, sync size, fed through IncrementalEngine.feed)
    "n8": (lambda: synthetic_dag(8, 300, seed=3)[0], 37, False),
    "n5": (lambda: synthetic_dag(5, 97, seed=3)[0], 10, False),
    "n8_feed": (lambda: synthetic_dag(8, 300, seed=3)[0], 37, True),
    # Power-law peer selection (weights rank**-1.2): a skewed topology.
    "powerlaw-16": (lambda: simulate_views(
        16, 800, selector="powerlaw", alpha=1.2, seed=5)[0], 100, False),
    "powerlaw-32": (lambda: simulate_views(
        32, 3200, selector="powerlaw", alpha=1.2, seed=5)[0], 400, False),
    "silent-16": (lambda: _silent_dag(16, 800, seed=6), 100, False),
}


@pytest.mark.parametrize("case", list(ENGINE_CASES))
def test_engine_matches_reference(case):
    """Batched ingest with run() between batches equals the plain
    reference over the whole DAG, across capacity doubling and
    chain-bucket growth; `_feed` cases drive the pipelined
    dispatch/collect loop (IncrementalEngine.feed) instead."""
    build, bs, feed = ENGINE_CASES[case]
    dag = build()
    eng = IncrementalEngine(dag.n, capacity=64, block=64, k_capacity=8)
    if feed:
        assert list(eng.feed(dag, bs))[-1] == dag.e
    else:
        feed_engine(eng, dag, bs)
    ref = assert_engine_matches_reference(eng, dag)
    assert (ref.rr >= 0).sum() > dag.e // 4, case


TS_BASE = 1_700_000_000_000_000_000  # no real timestamp's hi half is 0


@pytest.mark.parametrize(
    "n,e,sync,chunk,case",
    [(4, 300, 10, 4, "full_chunks"), (16, 600, 50, 4, "partial_last_chunk"),
     (16, 600, 50, 4, "nothing_received"), (4, 1500, 1500, 4, "cb_overflow"),
     (4, 600, 600, 520, "chunk_not_dividing_cb")],
    ids=lambda v: v if isinstance(v, str) else None)
def test_timestamp_chunks_follow_the_pass(monkeypatch, n, e, sync, chunk,
                                          case):
    """The fused kernel's timestamp stage runs ceil(newly / chunk)
    chunks of the lanes a dispatch received, none when it received
    nothing or overflowed the bucket cb; round received and consensus
    timestamps still equal the plain reference for every event."""
    monkeypatch.setattr(inc, "TS_CHUNK", chunk)
    dag, _ = synthetic_dag(n, e, seed=3)
    ts = TS_BASE + np.arange(e, dtype=np.int64)
    ring = SpanRing(1 << 14)
    eng = IncrementalEngine(n, capacity=2048 if sync > 1024 else 64,
                            block=64, k_capacity=8, trace=ring)
    pulls = []  # (newly, cb, tc, chunks run, cts planes, redo) per pull
    resize = eng._resize_windows

    def record(pp, packed, t_end, newly):
        ran = -(-newly // pp.tc) if newly <= pp.cb else 0
        cb, tc = pp.cb, pp.tc
        cts = packed[-2 * cb:].reshape(2, cb).copy()
        redo = resize(pp, packed, t_end, newly)
        pulls.append((newly, cb, tc, ran, cts, redo))
        return redo

    monkeypatch.setattr(eng, "_resize_windows", record)
    for k in range(0, e, sync):
        hi = min(k + sync, e)
        eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                         dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                         ts[k:hi])
        eng.run()
    eng.close()

    ref = reference.consensus(n, dag.self_parent[:e], dag.other_parent[:e],
                              dag.creator[:e], dag.index[:e], dag.coin[:e],
                              ts)
    assert (eng.rounds[:e] == ref.rounds).all()
    assert (eng.rr[:e] == ref.rr).all()
    got = ref.rr >= 0
    assert got.sum() > e // 4
    assert (eng.cts_ns[:e][got] == ref.cts[got]).all()

    for newly, cb, tc, ran, cts, _ in pulls:
        assert tc == min(chunk, cb)
        assert (cts[:, ran * tc:] == 0).all()  # no chunk past the count
        assert (cts[0, :newly] != 0).all() or not ran
    # Each collected pass's final pull, and its engine.apply span.
    final = [p for p in pulls if p[5] is None]
    applied = [sp["args"] for sp in ring.snapshot()
               if sp["name"] == "engine.apply"]
    assert [(a["newly"], a["ts_chunks"]) for a in applied] == [
        (p[0], p[3]) for p in final]
    hit = {
        "full_chunks": any(p[0] >= 2 * p[2] and p[0] % p[2] == 0
                           for p in final),
        "partial_last_chunk": any(p[0] > p[2] and p[0] % p[2]
                                  for p in final),
        "nothing_received": any(p[0] == 0 for p in final),
        "cb_overflow": any(p[0] > p[1] for p in pulls),
        "chunk_not_dividing_cb": any(p[1] % p[2] and p[3] * p[2] > p[1]
                                     for p in final),
    }
    assert hit[case], [(p[0], p[1], p[2], p[5]) for p in pulls]


def _reference_tables(dag, rows):
    """The reference's view of `dag`: (la[e, n] last ancestors, fd[e, n]
    first descendants, INT32_MAX for none), its result, and its witness
    and fame tables padded with empty rows to `rows`."""
    n, e, cr, idx = dag.n, dag.e, dag.creator, dag.index
    la = reference.last_ancestors(n, dag.self_parent, dag.other_parent,
                                  cr, idx)
    chain = np.full((n, int(idx.max()) + 1), -1, np.int32)
    chain[cr, idx] = np.arange(e)
    chain_la = np.where((chain >= 0)[:, :, None], la[np.maximum(chain, 0)],
                        inc.INT32_MAX)  # [n, K, n], monotone along K
    fd = (chain_la[:, :, cr] < idx[None, None, :]).sum(1).T
    fd = np.where(fd < np.bincount(cr, minlength=n), fd,
                  inc.INT32_MAX).astype(np.int32)
    ref = reference_of(dag)
    wt = reference_witness_table(ref, cr, rows)
    famous = np.zeros((rows, n), np.int32)
    famous[:len(ref.famous)] = ref.famous
    return la, fd, ref, wt, famous


def _fame_case(case, monkeypatch):
    """decide_fame_impl over a window with empty rows equals the same
    kernel run with the full static trip count, r - 1 steps."""
    dag, _ = synthetic_dag(8, 600, seed=3)
    n, sm, r = dag.n, 2 * dag.n // 3 + 1, 16
    la, fd, _, wt, _ = _reference_tables(dag, 2 + r)
    win = np.asarray(wt)[2:2 + r].copy()  # rows from a later rx0
    top = int(np.nonzero((win >= 0).any(1))[0][-1])
    assert 8 <= top < r - 1  # trailing rows are empty
    skip = 4
    if case == "fame_round_skip":
        win[skip] = -1
    elif case == "fame_all_empty":
        win[:] = -1
        top = 0

    def fame(w):
        run = jax.jit(functools.partial(kernels.decide_fame_impl, n=n,
                                        sm=sm, r=r))
        return np.asarray(run(jnp.asarray(w), la, fd, dag.index, dag.coin))

    got = fame(win)
    assert int(kernels.fame_steps(jnp.asarray(win))) == top
    with monkeypatch.context() as m:
        m.setattr(kernels, "fame_steps", lambda w: w.shape[0] - 1)
        full = fame(win)
    assert (got == full).all()
    if case == "fame_round_skip":
        # Steps past the empty row decided fame, so a sweep that
        # stopped at it would differ.
        assert (got[skip + 1:] != kernels.FAME_UNDEFINED).any()
    elif case == "fame_all_empty":
        assert (got == kernels.FAME_UNDEFINED).all()


def _rr_case(case):
    """_rr_chunked run through the last eligible round equals the full
    sweep over all iw rounds."""
    dag, _ = synthetic_dag(8, 600, seed=3)
    n, iw, rl = dag.n, 16, 64
    la, _, ref, wt_w, fam_w = _reference_tables(dag, iw)
    rounds = ref.rounds
    wt_valid = wt_w >= 0
    fmask = (fam_w == kernels.FAME_TRUE) & wt_valid
    e = dag.e
    au = -(-e // rl) * rl
    uid = np.zeros(au, np.int32)
    uid[:e] = np.arange(e)
    lane_ok = np.arange(au) < e
    elig = np.zeros(iw, bool)
    if case == "rr_eligible_ends_mid_window":
        elig[:5] = True
    elif case == "rr_eligibility_gap":
        elig[[0, 1, 3]] = True
    steps = int(inc._rr_steps(jnp.asarray(elig)))
    assert steps == (np.nonzero(elig)[0][-1] + 1 if elig.any() else 0)
    sweep = jax.jit(functools.partial(inc._rr_chunked, rl=rl, lanes=None))
    args = (jnp.where(jnp.asarray(lane_ok), -1, 0), jnp.int32(0), la,
            jnp.asarray(np.where(wt_valid, wt_w, 0)),
            dag.creator[uid], dag.index[uid], rounds[uid],
            jnp.asarray(lane_ok), jnp.int32(e), jnp.asarray(elig),
            jnp.asarray(fmask), jnp.asarray(fmask.sum(1)))
    got = np.asarray(sweep(*args, jnp.int32(steps)))
    full = np.asarray(sweep(*args, jnp.int32(iw)))
    assert (got == full).all()
    assert (got[:e] >= 0).any() == elig.any()


def _replay_case(monkeypatch):
    """On a small replay each pass's fame_steps is its window's last
    live row (the top round less rx0), and rr_steps runs from i0 to the
    first undecided round, none in a pass that decides no round and
    brings no event of a decided one; both ride the engine.apply span."""
    n, e, sync = 16, 600, 50
    dag, _ = synthetic_dag(n, e, seed=3)
    ring = SpanRing(1 << 14)
    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8,
                            trace=ring)
    passes = []  # (pass_counts, newly, fame_steps and rr_steps expected)
    apply = eng._apply_delta

    def record(pp, packed, newly):
        first_undec = eng._prev_first_undec
        delta = apply(pp, packed, newly)
        top = int(eng.rounds[:pp.e].max())
        i0 = min(first_undec, int(eng.rounds[pp.new_ids].min()) + 1)
        assert eng.undecided_rounds  # else the device's bound is the window
        rr_steps = min(max(eng._prev_first_undec - i0, 0), pp.iw)
        passes.append((pp.counts, newly, (max(top - pp.rx0, 0), rr_steps)))
        return delta

    monkeypatch.setattr(eng, "_apply_delta", record)
    for k in range(0, e, sync):
        hi = min(k + sync, e)
        eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                         dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                         np.arange(k, hi))
        eng.run()
    eng.close()
    for pc, newly, want in passes:
        assert (pc["fame_steps"], pc["rr_steps"]) == want
        assert pc["rr_steps"] or not newly
    # The bounds engaged: passes that stepped over no round, and passes
    # whose fame loop stopped short of the window.
    assert any(pc["rr_steps"] == 0 for pc, _, _ in passes)
    assert any(0 < pc["rr_steps"] for pc, _, _ in passes)
    assert all(pc["fame_steps"] < pc["iw"] - 1 for pc, _, _ in passes)
    applied = [sp["args"] for sp in ring.snapshot()
               if sp["name"] == "engine.apply"]
    assert [(a["fame_steps"], a["rr_steps"]) for a in applied] == [
        (pc["fame_steps"], pc["rr_steps"]) for pc, _, _ in passes]


@pytest.mark.parametrize(
    "case", ["fame_trailing_empty_rows", "fame_round_skip", "fame_all_empty",
             "rr_none_eligible", "rr_eligible_ends_mid_window",
             "rr_eligibility_gap", "replay_counts"])
def test_window_loops_stop_at_the_last_row_with_work(monkeypatch, case):
    """The fused kernel's fame vote loop and round-received sweep run
    through the window's last row that can hold work, not over all W
    rows, and give what the full trip count gives."""
    if case.startswith("fame_"):
        _fame_case(case, monkeypatch)
    elif case.startswith("rr_"):
        _rr_case(case)
    else:
        _replay_case(monkeypatch)


GRAPHS = {"round": (build_round_graph, 4),
          "consensus": (build_consensus_graph, 7),
          "funky": (build_funky_graph, 3)}


@pytest.mark.parametrize("check", ["interleaved", "rounds", "fame", "order"])
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_tpu_graph_matches_host(graph, check):
    """TpuHashgraph reproduces the host engine on the reference fixture
    graphs. `interleaved` runs consensus every few inserts and checks
    rounds, witness sets, fame, consensus order and blocks; the other
    checks run it once over the whole graph: `rounds` rounds and
    witness sets, `fame` fame and the undecided rounds, `order` round
    received, consensus timestamps, order and blocks."""
    build, every = GRAPHS[graph]
    if check != "interleaved":
        h, b, t = make_tpu_twin(build)
        _one_pass_parity(h, b, t, check)
        return
    h, b = build()
    h.divide_rounds()
    h.decide_fame()
    h.find_order()

    participants = b.participants()
    t = TpuHashgraph(participants, InmemStore(participants, CACHE),
                     capacity=64, block=64)
    for k, ev in enumerate(b.ordered_events):
        t.insert_event(ev, True)
        if (k + 1) % every == 0:
            t.run_consensus()
    t.run_consensus()

    for ev in b.ordered_events:
        x = ev.hex()
        assert t.round(x) == h.round(x), b.get_name(x)
        assert t.witness(x) == h.witness(x), b.get_name(x)
        assert t.round_received(x) == h.round_received(x), b.get_name(x)
    for r in range(h.store.last_round() + 1):
        assert set(t.store.round_witnesses(r)) == set(
            h.store.round_witnesses(r)), f"round {r}"
        hri = h.store.get_round(r)
        tri = t.store.get_round(r)
        for w in hri.witnesses():
            assert tri.events[w].famous == hri.events[w].famous, (
                f"fame mismatch {b.get_name(w)} round {r}")
    assert t.consensus_events() == h.consensus_events()
    assert t.last_consensus_round == h.last_consensus_round
    assert t.pending_loaded_events == h.pending_loaded_events
    assert t.consensus_transactions == h.consensus_transactions
    assert set(t.undetermined_events) == set(h.undetermined_events)
    for r in range(h.store.last_round() + 1):
        try:
            hb = h.store.get_block(r)
        except Exception:
            continue
        tb = t.store.get_block(r)
        assert tb.hash() == hb.hash(), f"block {r}"


def _one_pass_parity(h, b, t, check):
    if check == "rounds":
        for ev in b.ordered_events:
            x = ev.hex()
            assert t.round(x) == h.round(x), b.get_name(x)
            assert t.witness(x) == h.witness(x), b.get_name(x)
        for r in range(h.store.last_round() + 1):
            assert set(t.store.round_witnesses(r)) == set(
                h.store.round_witnesses(r)), f"round {r}"
    elif check == "fame":
        for r in range(h.store.last_round() + 1):
            hri = h.store.get_round(r)
            tri = t.store.get_round(r)
            for w in hri.witnesses():
                assert tri.events[w].famous == hri.events[w].famous, (
                    f"fame mismatch {b.get_name(w)} round {r}")
        assert sorted(set(t.undecided_rounds)) == sorted(
            set(h.undecided_rounds))
        assert t.last_consensus_round == h.last_consensus_round
    else:
        for ev in b.ordered_events:
            x = ev.hex()
            rr = h.round_received(x)
            assert t.round_received(x) == rr, b.get_name(x)
            if rr >= 0:
                assert (t.store.get_event(x).consensus_timestamp.ns
                        == h.store.get_event(x).consensus_timestamp.ns), (
                    b.get_name(x))
        assert t.consensus_events() == h.consensus_events()
        rounds = sorted({h.store.get_event(x).round_received
                         for x in h.consensus_events()})
        for r in rounds:
            hb, tb = h.store.get_block(r), t.store.get_block(r)
            assert tb.transactions == hb.transactions, f"block {r}"
            assert tb.hash() == hb.hash(), f"block {r}"


def test_tpu_graph_consensus_timestamps():
    """Consensus timestamps (median over famous-witness first
    descendants) must match the host engine exactly — they are the
    second consensus sort key."""
    h, b, t = make_tpu_twin(build_consensus_graph)
    for x in h.consensus_events():
        he = h.store.get_event(x)
        te = t.store.get_event(x)
        assert te.consensus_timestamp.ns == he.consensus_timestamp.ns, (
            b.get_name(x))


def test_gossip_tpu_engine():
    """4-node gossip over the inmem transport with the device engine
    deciding consensus — reference node_test.go:396-407 with the
    JaxStore-sibling integration (SURVEY §7 step 3)."""
    nodes = make_nodes(4, "inmem", engine="tpu")
    for node in nodes:
        assert isinstance(node.core.hg, TpuHashgraph)
    # Generous budget: the engine jit-compiles several bucketed window
    # shapes on first use, and under a full-suite run those compiles
    # contend with other tests' caches (the isolated run sits near 110s).
    run_gossip(nodes, target_round=5, timeout=300.0)
    check_gossip(nodes)


def test_tpu_graph_get_frame_matches_host():
    """GetFrame (the fast-sync snapshot, reference hashgraph.go:900-1002)
    served from device-backed state must equal the host engine's frame:
    same roots and the same events in the same (topological) order —
    the order matters because frames are replayed in order during
    fast-sync."""
    h, b, t = make_tpu_twin(build_consensus_graph)

    hf = h.get_frame()
    tf = t.get_frame()
    assert [e.hex() for e in tf.events] == [e.hex() for e in hf.events]
    assert set(tf.roots) == set(hf.roots)
    for pk, hr in hf.roots.items():
        tr = tf.roots[pk]
        assert (tr.x, tr.y, tr.index, tr.round, tr.others) == (
            hr.x, hr.y, hr.index, hr.round, hr.others), pk


def test_run_unlocked_appends_interleave():
    """The live node releases the core lock around the device-result
    wait (node/node.py _core_unlocked), so appends can land MID-run.
    The pass must operate on its snapshot — neither corrupting results
    for the dispatched batch nor losing the interleaved events. Final
    state must equal a serial engine fed the same stream."""
    import contextlib

    from babble_tpu.ops.dag import synthetic_dag as sdag

    n, e, bs = 8, 400, 57
    dag, _ = sdag(n, e, seed=9)
    batches = [(k, min(k + bs, e)) for k in range(0, e, bs)]

    def feed(g, k, hi):
        g.append_batch(
            dag.self_parent[k:hi], dag.other_parent[k:hi],
            dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
            np.arange(k, hi))

    ref = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    for k, hi in batches:
        feed(ref, k, hi)
        ref.run()

    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    state = {"next": 1}

    @contextlib.contextmanager
    def interleave():
        # Fires exactly where the node's lock release does: during the
        # blocking pull. Inject the next batch right there.
        if state["next"] < len(batches):
            k, hi = batches[state["next"]]
            state["next"] += 1
            feed(eng, k, hi)
        yield

    feed(eng, *batches[0])
    for _ in range(3 * len(batches)):
        eng.run(unlocked=interleave)
        if state["next"] >= len(batches):
            break
    eng.run()  # drain whatever the last interleave injected

    assert (eng.rounds[:e] == ref.rounds[:e]).all()
    assert (eng.witness[:e] == ref.witness[:e]).all()
    assert (eng.rr[:e] == ref.rr[:e]).all()
    assert (eng.cts_ns[:e] == ref.cts_ns[:e]).all()
    assert (eng.famous == ref.famous).all()
    assert eng.undecided_rounds == ref.undecided_rounds


def test_run_retries_after_transient_failure():
    """A pass that dies mid-flight (device lost, preemption) must not
    orphan its batch: the snapshot is restored, the node's consensus
    worker retries, and the retry produces the same results as a
    never-failed engine."""
    import contextlib

    from babble_tpu.ops.dag import synthetic_dag as sdag

    n, e = 8, 200
    dag, _ = sdag(n, e, seed=4)

    def feed(g, k, hi):
        g.append_batch(
            dag.self_parent[k:hi], dag.other_parent[k:hi],
            dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
            np.arange(k, hi))

    ref = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    feed(ref, 0, 120)
    ref.run()
    feed(ref, 120, e)
    ref.run()

    eng = IncrementalEngine(n, capacity=64, block=64, k_capacity=8)
    feed(eng, 0, 120)

    @contextlib.contextmanager
    def device_lost():
        raise RuntimeError("device lost")
        yield  # pragma: no cover

    with pytest.raises(RuntimeError):
        eng.run(unlocked=device_lost)
    eng.run()  # retry re-mirrors the restored batch
    feed(eng, 120, e)
    eng.run()

    assert (eng.rounds[:e] == ref.rounds[:e]).all()
    assert (eng.witness[:e] == ref.witness[:e]).all()
    assert (eng.rr[:e] == ref.rr[:e]).all()
    assert (eng.famous == ref.famous).all()
    assert eng.undecided_rounds == ref.undecided_rounds


# ---------------------------------------------------------------- reset


def _assert_consensus_parity(h, t, hexes, label=lambda x: x):
    assert t.store.last_round() == h.store.last_round()
    for x in hexes:
        assert t.round(x) == h.round(x), label(x)
        assert t.witness(x) == h.witness(x), label(x)
        assert t.round_received(x) == h.round_received(x), label(x)
    for r in range(h.store.last_round() + 1):
        assert set(t.store.round_witnesses(r)) == set(
            h.store.round_witnesses(r)), f"round {r}"
        try:
            hri = h.store.get_round(r)
        except StoreError:
            # Post-reset stores start at the roots' round; both engines
            # must agree on which rounds exist at all.
            with pytest.raises(StoreError):
                t.store.get_round(r)
            continue
        tri = t.store.get_round(r)
        for w in hri.witnesses():
            assert tri.events[w].famous == hri.events[w].famous, (
                f"fame mismatch round {r}")
    assert t.consensus_events() == h.consensus_events()
    assert t.last_consensus_round == h.last_consensus_round


def test_tpu_reset():
    """Manual-roots reset then tail replay on the device engine — the
    mirror of test_hashgraph.py::test_reset (reference
    hashgraph_test.go:1144): Roots with offset chain bases (index=4,
    round=2) and an Others entry, followed by continued consensus over
    the replayed tail, bit-identical to the host engine."""
    h, b, t = make_tpu_twin(build_consensus_graph)
    i = b.index
    evs = ["g1", "g0", "g2", "g10", "g21", "o02", "g02", "h1", "h0", "h2"]

    def mk_roots():
        return {
            h.reverse_participants[0]: Root(
                x=i["f02b"], y=i["g1"], index=4, round=2,
                others={i["o02"]: i["f21"]},
            ),
            h.reverse_participants[1]: Root(
                x=i["f10"], y=i["f02b"], index=4, round=2),
            h.reverse_participants[2]: Root(
                x=i["f21"], y=i["g1"], index=4, round=2),
        }

    def backups(g):
        out = []
        for name in evs:
            ev = g.store.get_event(i[name])
            out.append(Event(ev.body, r=ev.r, s=ev.s))
        return out

    hb, tb = backups(h), backups(t)
    h.reset(mk_roots())
    t.reset(mk_roots())
    for eh, et in zip(hb, tb):
        h.insert_event(eh, False)
        t.insert_event(et, False)
    assert h.known() == {0: 8, 1: 7, 2: 7}
    assert t.known() == h.known()

    h.divide_rounds()
    h.decide_fame()
    h.find_order()
    t.run_consensus()
    _assert_consensus_parity(h, t, [i[name] for name in evs], b.get_name)


def test_tpu_reset_from_frame():
    """get_frame -> reset -> full frame replay on the device engine
    (reference hashgraph_test.go:1302): known(), rounds, witnesses,
    fame trileans, and the re-derived last_consensus_round must all
    match the host engine performing the same reset."""
    h, b, t = make_tpu_twin(build_consensus_graph)
    hf = h.get_frame()
    tf = t.get_frame()

    h.reset(hf.roots)
    t.reset(tf.roots)
    for ev in hf.events:
        h.insert_event(Event(ev.body, r=ev.r, s=ev.s), False)
    for ev in tf.events:
        t.insert_event(Event(ev.body, r=ev.r, s=ev.s), False)

    assert h.known() == {0: 8, 1: 7, 2: 7}
    assert t.known() == h.known()

    h.divide_rounds()
    h.decide_fame()
    h.find_order()
    t.run_consensus()
    assert h.last_consensus_round == 1
    _assert_consensus_parity(
        h, t, [e.hex() for e in hf.events], b.get_name)


def _reset_from_frame_engine():
    _, _, t = make_tpu_twin(build_consensus_graph)
    frame = t.get_frame()
    t.reset(frame.roots)
    for ev in frame.events:
        t.insert_event(Event(ev.body, r=ev.r, s=ev.s), False)
    t.run_consensus()
    return t.engine


def _fed_engine(build, bs, mesh=None):
    dag = build()
    return feed_engine(
        IncrementalEngine(dag.n, capacity=256, block=64, k_capacity=8,
                          mesh=mesh, mesh_axis="sp"), dag, bs)


def _gossip16():
    return synthetic_dag(16, 1000, seed=11)[0]


def _sp4_engine():
    from babble_tpu.node.core import engine_mesh

    return _fed_engine(_gossip16, 200, mesh=engine_mesh(jax.devices(), 16, 4))


SEES_CASES = {
    "n8": lambda: _fed_engine(ENGINE_CASES["n8"][0], 37),
    "n16": lambda: _fed_engine(_gossip16, 200),
    "powerlaw-16": lambda: _fed_engine(*ENGINE_CASES["powerlaw-16"][:2]),
    "silent-16": lambda: _fed_engine(*ENGINE_CASES["silent-16"][:2]),
    "reset-from-frame": _reset_from_frame_engine,
    "sp4": _sp4_engine,
}


@pytest.mark.parametrize("case", list(SEES_CASES))
def test_first_descendant_row_answers_witness_sees(case):
    """The identity the fused kernel's timestamp stage rests on: for
    every event x and every witness w of the engine's witness table,
    la[w, creator_x] >= index_x exactly when fd[x, creator_w] <=
    index_w, both read from the engine's resident la and rank cube
    (_FdRows)."""
    eng = SEES_CASES[case]()
    e = eng.e
    la = np.asarray(eng._la)[:e]
    fd = np.asarray(inc._FdRows(eng._ranks, jnp.asarray(eng.chain_len),
                                eng._cr_d, eng._idx_d)[jnp.arange(e)])
    wt = eng.witness_table()
    w = wt[wt >= 0]
    cr, ix = eng.creator[:e], eng.index[:e]
    by_la = la[w][:, cr] >= ix[None, :]  # [witness, x]
    by_fd = fd[:, eng.creator[w]].T <= eng.index[w][:, None]
    assert by_la.any() and not by_la.all(), case
    assert (by_la == by_fd).all(), case


def test_append_batch_vectorized_matches_serial():
    """The vectorized append_batch (one slice assignment per staging
    column) must leave the engine bit-identical to per-event appends —
    including interleaved-creator batches, capacity doubling, and
    chain-bucket growth — and reject the same invalid batches."""
    dag, _ = synthetic_dag(8, 400, seed=3)
    ts = np.arange(400, dtype=np.int64) * 7 + 100
    serial = IncrementalEngine(8, capacity=64, block=64, k_capacity=8)
    batched = IncrementalEngine(8, capacity=64, block=64, k_capacity=8)
    for k in range(400):
        serial.append(int(dag.self_parent[k]), int(dag.other_parent[k]),
                      int(dag.creator[k]), int(dag.index[k]),
                      bool(dag.coin[k]), int(ts[k]))
    lo = 0
    for size in (1, 3, 17, 64, 5, 127, 400):
        hi = min(400, lo + size)
        first = batched.append_batch(
            dag.self_parent[lo:hi], dag.other_parent[lo:hi],
            dag.creator[lo:hi], dag.index[lo:hi], dag.coin[lo:hi],
            ts[lo:hi])
        assert first == lo
        lo = hi
    for name in ("self_parent", "other_parent", "creator", "index",
                 "coin", "root_base", "ts_ns", "chain", "chain_len",
                 "rounds", "witness", "rr", "cts_ns"):
        assert np.array_equal(getattr(serial, name),
                              getattr(batched, name)), name
    assert serial.e == batched.e
    assert serial._new_since_run == batched._new_since_run

    with pytest.raises(ValueError):
        batched.append_batch(
            np.array([-1, 5]), np.array([-1, -1]), np.array([0, 0]),
            np.array([999, 1000]), np.array([0, 0]), np.array([1, 2]))

    serial.run()
    batched.run()
    assert np.array_equal(serial.rounds[:serial.e],
                          batched.rounds[:batched.e])
    assert np.array_equal(serial.rr[:serial.e], batched.rr[:batched.e])


def test_tpu_insert_wire_batch_matches_serial_inserts():
    """Device-direct ingest seam: TpuHashgraph.insert_wire_batch (host
    checks per event, ONE vectorized engine append) must equal the
    serial insert_event loop — engine state, store contents, and the
    consensus it then decides."""
    h, b = build_consensus_graph()
    participants = b.participants()

    serial = TpuHashgraph(participants, InmemStore(participants, CACHE),
                          capacity=64, block=64)
    batched = TpuHashgraph(participants, InmemStore(participants, CACHE),
                           capacity=64, block=64)
    evs = b.ordered_events
    for ev in evs:
        serial.insert_event(Event(ev.body, r=ev.r, s=ev.s), True)
    # two chunks, split mid-stream, cloned events
    mid = len(evs) // 2
    batched.insert_wire_batch(
        [Event(e.body, r=e.r, s=e.s) for e in evs[:mid]])
    batched.insert_wire_batch(
        [Event(e.body, r=e.r, s=e.s) for e in evs[mid:]])

    assert serial.known() == batched.known()
    assert serial.undetermined_events == batched.undetermined_events
    eng_s, eng_b = serial.engine, batched.engine
    for name in ("self_parent", "other_parent", "creator", "index",
                 "coin", "ts_ns", "chain", "chain_len"):
        assert np.array_equal(getattr(eng_s, name),
                              getattr(eng_b, name)), name
    serial.run_consensus()
    batched.run_consensus()
    assert serial.store.consensus_events() == \
        batched.store.consensus_events()
    assert serial.last_consensus_round == batched.last_consensus_round


@pytest.mark.parametrize("phase", ["n64", "northstar"])
def test_chip_smoke_checks_on_cpu(phase):
    """chip_smoke's n64 (device engine vs host engine on signed gossip)
    and northstar (engine vs the plain reference) phases, with their
    checks, at a small size."""
    import chip_smoke

    out = getattr(chip_smoke, phase)(n=8, e=600)
    assert out["events"] == 600 and out["committed"] > 0

"""Off-GIL process runtime tests (docs/runtime.md): the shared-memory
columnar hand-off is pickle-free and byte-identical, the procs verify
plane delivers the thread path's exact memo/failure-position contract,
a killed worker's in-flight chunk is dropped + re-verified inline and
the worker respawned, worker telemetry merges into a parent scrape
with a process label, and a mixed threads/procs cluster commits
byte-identical blocks."""

from __future__ import annotations

import os
import signal
import time
from multiprocessing import shared_memory

import pytest

from babble_tpu import crypto
from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.event import Event
from babble_tpu.net import InmemTransport
from babble_tpu.net.columnar import ColumnarEvents, WireFormatError
from babble_tpu.net.inmem_transport import connect_all
from babble_tpu.node import Node, ingest
from babble_tpu.node import runtime as rt
from babble_tpu.node.config import test_config as fast_config
from babble_tpu.proxy import InmemAppProxy
from babble_tpu.telemetry import Registry, promtext

from test_node import CACHE, check_gossip, make_keyed_peers, run_gossip

pytestmark = pytest.mark.skipif(
    not hasattr(os, "sched_getaffinity"),
    reason="procs runtime targets Linux schedulers")


@pytest.fixture(autouse=True)
def _fresh_pool():
    """Each test starts from a cold process pool and leaves no worker
    processes behind for the rest of the suite."""
    rt.reset_for_tests()
    yield
    rt.reset_for_tests()


def _signed_events(count, seed=321, tag=b"rt"):
    key = crypto.key_from_seed(seed)
    pub = crypto.pub_key_bytes(key)
    events = []
    for i in range(count):
        ev = Event.new([tag + b"-%d" % i], ["p0", "p1"], pub, i)
        ev.sign(key)
        ev._sig_ok = None  # drop sign()'s memo: force real verification
        events.append(ev)
    return key, events


# ------------------------------------------------- shared-memory frames


def test_columnar_roundtrip_through_shared_memory_pickle_free():
    """The PR 7 columnar frame crosses a shared_memory segment with no
    pickling: the receiving side decodes VIEWS over the segment's
    buffer (zero-copy), the columns are byte-identical, and re-encoding
    reproduces the original frame bit for bit."""
    _, events = _signed_events(24)
    ce = ColumnarEvents.from_wire_events([ev.to_wire() for ev in events])
    frame = ce.encode()

    shm = shared_memory.SharedMemory(create=True, size=len(frame))
    try:
        shm.buf[:len(frame)] = frame
        # Decode straight over the segment's memoryview — what a
        # worker does. No bytes() copy, no pickle anywhere.
        view = memoryview(shm.buf)[:len(frame)]
        dec = ColumnarEvents.decode(view)
        # The integer columns are numpy VIEWS into the segment, not
        # owned copies: zero-copy is structural, not incidental.
        assert dec.cid.base is not None
        assert dec.ts_ns.base is not None
        for a, b in ((dec.cid, ce.cid), (dec.idx, ce.idx),
                     (dec.sp_idx, ce.sp_idx), (dec.op_cid, ce.op_cid),
                     (dec.op_idx, ce.op_idx), (dec.ts_ns, ce.ts_ns),
                     (dec.tx_counts, ce.tx_counts),
                     (dec.tx_lens, ce.tx_lens)):
            assert a.tolist() == b.tolist()
        assert bytes(dec.sigs) == bytes(ce.sigs)
        assert bytes(dec.tx_blob) == bytes(ce.tx_blob)
        assert dec.encode() == frame
        # Release every view over the segment before close() — a live
        # export makes close() raise BufferError by design.
        del dec, a, b
        view.release()
    finally:
        shm.close()
        shm.unlink()


def test_decode_validate_false_skips_only_integrity_sweeps():
    """validate=False (the post-worker-validation fast path) must skip
    ONLY the O(n) consistency sweeps — the structural length check the
    views depend on still runs."""
    _, events = _signed_events(8)
    frame = ColumnarEvents.from_wire_events(
        [ev.to_wire() for ev in events]).encode()
    a = ColumnarEvents.decode(frame)
    b = ColumnarEvents.decode(frame, validate=False)
    assert a.encode() == b.encode() == frame
    with pytest.raises(WireFormatError):
        ColumnarEvents.decode(frame[:-1], validate=False)


# ------------------------------------------------------- verify plane


def test_procs_verify_parity_including_failure_position():
    """The procs plane delivers the serial/thread contract exactly:
    valid memos True, a corrupted signature False at the identical
    batch position, and a malformed creator point left UNSET so the
    insert loop re-raises at the serial path's position."""
    key, events = _signed_events(16)
    events[3].r = int(events[3].r) ^ 1

    ingest.verify_events(events, workers=2, runtime="procs")
    assert rt.active_pool() is not None, "procs path did not engage"
    assert [ev._sig_ok for ev in events] == \
        [True] * 3 + [False] + [True] * 12

    # Same batch through the thread path: memo-for-memo identical.
    for ev in events:
        ev._sig_ok = None
    ingest.verify_events(events, workers=2, runtime="threads")
    assert [ev._sig_ok for ev in events] == \
        [True] * 3 + [False] + [True] * 12

    # Malformed creator: verdict None -> memo unset (both runtimes).
    _, batch = _signed_events(9, seed=77, tag=b"mc")
    batch[0].body.creator = b"\x00" * 10
    ingest.verify_events(batch, workers=2, runtime="procs")
    assert batch[0]._sig_ok is None
    assert all(ev._sig_ok is True for ev in batch[1:])

    # r outside 32 bytes is an invalid signature (False), exactly as
    # crypto.verify reports it — decided parent-side, no round trip.
    _, batch2 = _signed_events(9, seed=78, tag=b"ov")
    batch2[0].r = 1 << 300
    ingest.verify_events(batch2, workers=2, runtime="procs")
    assert batch2[0]._sig_ok is False
    assert all(ev._sig_ok is True for ev in batch2[1:])


def test_procs_workers_never_load_jax():
    """A chip belongs to one process: the spawned verify workers must
    never load JAX (let alone initialize a backend), or a procs-mode
    tpu node would fight its own workers for the chip."""
    key, events = _signed_events(8, seed=79, tag=b"jx")
    ingest.verify_events(events, workers=2, runtime="procs")
    assert all(ev._sig_ok is True for ev in events)
    for w in rt.active_pool().workers():
        path = f"/proc/{w.proc.pid}/maps"
        if not os.path.exists(path):
            pytest.skip("no /proc maps to inspect")
        with open(path) as f:
            maps = f.read()
        assert "jaxlib" not in maps and "libtpu" not in maps, w.name


def test_procs_worker_killed_midbatch_drops_and_reverifies_inline():
    """Worker death with a chunk in flight mirrors the cancelled-chunk
    contract (PR 16): the chunk observes its queued wait, counts a
    drop on the shared verify_pool instrument, and is re-verified
    inline so the memos still land — and the supervisor respawns the
    worker for the next batch, counting the restart."""
    key, events = _signed_events(16, seed=91)
    events[3].r = int(events[3].r) ^ 1

    pool = rt.get_pool(2)
    assert pool is not None
    workers = pool.workers()  # spawn both before the kill
    os.kill(workers[0].proc.pid, signal.SIGKILL)
    workers[0].proc.join(timeout=5.0)

    # Suppress the dispatch-time respawn so the dead worker's chunk is
    # genuinely in flight when the death is observed (the respawn-
    # before-dispatch path is supervision working TOO well for this
    # test's purpose).
    real_ensure = pool._ensure
    pool._ensure = lambda i, count_restart=True: pool._workers[i % pool.size]

    inst = ingest._pool_instrument()
    before = inst.snapshot()
    restarts_before = pool._m_restarts.value
    try:
        ingest.verify_events(events, workers=2, runtime="procs")
    finally:
        pool._ensure = real_ensure

    after = inst.snapshot()
    # Two chunks dispatched; the dead worker's chunk waited, dropped,
    # and fell back inline. Both chunks' waits are observed.
    assert after["dropped"] == before["dropped"] + 1
    assert after["waits"] >= before["waits"] + 2
    assert [ev._sig_ok for ev in events] == \
        [True] * 3 + [False] + [True] * 12

    # Next batch: the supervisor respawns the dead worker and the
    # restart is counted; delivery is back to the no-drop path.
    for ev in events:
        ev._sig_ok = None
    ingest.verify_events(events, workers=2, runtime="procs")
    assert pool._m_restarts.value >= restarts_before + 1
    assert [ev._sig_ok for ev in events] == \
        [True] * 3 + [False] + [True] * 12


# -------------------------------------------------------- decode plane


def test_decode_offload_roundtrip_and_malformed_frame():
    """Large frames route through a worker for validation and decode
    identically; a frame whose corruption only the integrity sweeps
    catch still raises WireFormatError through the offload path."""
    key, events = _signed_events(16, seed=55)
    ingest.verify_events(events, workers=2, runtime="procs")  # warm pool

    _, big = _signed_events(600, seed=56, tag=b"z" * 20)
    frame = ColumnarEvents.from_wire_events(
        [ev.to_wire() for ev in big]).encode()
    assert len(frame) >= rt._MIN_DECODE_BYTES
    dec = rt.decode_columnar(frame)
    assert dec.encode() == frame

    # Corrupt one tx_len: the frame's LENGTH is unchanged (the
    # structural check passes) — only the worker-side integrity sweep
    # can reject it.
    import struct

    bad = bytearray(frame)
    off = 4 + 17 + 600 * (5 * 4 + 8 + 64 + 4)
    struct.pack_into("<i", bad, off, 9999)
    with pytest.raises(WireFormatError):
        rt.decode_columnar(bytes(bad))


# -------------------------------------------------- cross-process scrape


def test_worker_registry_scrape_merges_with_process_label():
    """Worker registries cross the pipe and mirror into the parent
    registry with a process label: the batch-size histogram, the
    chunk/event counters, and per-process CPU seconds all render in
    one parse-valid exposition."""
    key, events = _signed_events(16, seed=44)
    ingest.verify_events(events, workers=2, runtime="procs")

    reg = Registry()
    answered = rt.scrape_children(reg)
    assert answered == 2
    text = reg.render()
    samples, _ = promtext.parse(text)

    cpu = {lb["process"]: v
           for lb, v in samples.get("babble_process_cpu_seconds_total", [])}
    assert set(cpu) == {"verify-0", "verify-1"}
    assert all(v > 0 for v in cpu.values())

    chunks = {lb["process"]: v
              for lb, v in samples.get("babble_worker_chunks_total", [])}
    assert set(chunks) == {"verify-0", "verify-1"}
    assert sum(chunks.values()) >= 2  # both chunks of the batch

    # The worker's batch-size histogram arrives process-labelled, so
    # it never collides with the parent's own unlabelled family.
    assert any(lb.get("process") in ("verify-0", "verify-1")
               for lb, _v in samples.get(
                   "babble_verify_batch_size_count", []))

    # Throttle: an immediate re-scrape is skipped (no pipe traffic at
    # scrape cadence), a post-interval one answers again.
    assert rt.scrape_children(reg) == 0


# ------------------------------------------------- mixed-runtime cluster


def _make_mixed_nodes(runtimes):
    transports = [InmemTransport(f"addr{i}", timeout=2.0)
                  for i in range(len(runtimes))]
    connect_all(transports)
    entries = make_keyed_peers(len(runtimes), addr_fn=lambda i: f"addr{i}")
    by_addr = {t.local_addr(): t for t in transports}
    peers = [p for _, p in entries]
    participants = {p.pub_key_hex: i for i, p in enumerate(peers)}
    nodes = []
    for i, (key, peer) in enumerate(entries):
        conf = fast_config(heartbeat=0.01)
        conf.runtime = runtimes[i]
        # Force a real pool even on a 1-core runner: the point is
        # exercising the procs path, not auto-sizing it.
        conf.verify_workers = 2
        store = InmemStore(participants, CACHE)
        node = Node(conf, i, key, peers, store,
                    by_addr[peer.net_addr], InmemAppProxy())
        node.init()
        nodes.append(node)
    return nodes


def test_mixed_runtime_cluster_commits_byte_identical_blocks():
    """A 3-node cluster with one procs node and two threads nodes
    reaches consensus on byte-identical event/tx sequences — the
    runtime is an execution detail, invisible to the protocol — and
    stays byte-identical through a worker SIGKILL mid-run."""
    nodes = _make_mixed_nodes(["procs", "threads", "threads"])
    try:
        run_gossip(nodes, target_round=6, timeout=120.0, shutdown=False)
        # Kill a verify worker while gossip is live: supervision must
        # absorb it (drop + inline re-verify + respawn) without any
        # consensus divergence. The net keeps running (run_gossip
        # already spawned the node loops — don't start them twice),
        # so just keep bombarding until the next round target.
        pool = rt.active_pool()
        if pool is not None:
            os.kill(pool.workers()[0].proc.pid, signal.SIGKILL)
        deadline = time.monotonic() + 120.0
        i = 0
        while time.monotonic() < deadline:
            nodes[i % 3].submit_tx(b"post-kill tx %d" % i)
            i += 1
            if all((nd.core.get_last_consensus_round_index() or 0) >= 10
                   for nd in nodes):
                break
            time.sleep(0.02)
        else:
            rounds = [nd.core.get_last_consensus_round_index()
                      for nd in nodes]
            raise AssertionError(f"post-kill rounds {rounds} < 10")
    finally:
        for nd in nodes:
            nd.shutdown()
    check_gossip(nodes)
    # The procs node really ran the procs plane.
    assert nodes[0].core.runtime == "procs"
    assert nodes[1].core.runtime == "threads"


def test_resolve_runtime_rejects_unknown():
    assert rt.resolve_runtime(None) == "threads"
    assert rt.resolve_runtime("procs") == "procs"
    with pytest.raises(ValueError):
        rt.resolve_runtime("fibers")

"""Plain virtual voting over a DAG that one chip cannot hold: the
semantics of benchmark/reference.py, with its coordinate tables sharded
over the cell's chips and every dense step in blocks.

At n=4096 over 360,448 events the last-ancestor table `la` [e, n] is
5.9 GB and the per-chain table `LA` [n, K, n] 8.6 GB; `la` is split by
column and `LA` by chain over a mesh of the given devices. A strong-sight
count sums over the split participant axis, so each device counts its
columns and the compiler adds the partial counts. Like reference.py it
imports nothing of babble_tpu; counts are exact integers.

Two steps are organised differently from reference.py, with the same
results:

- Rounds. An event that strongly sees a supermajority of round-r
  witnesses has every self-descendant doing so too (la only grows along
  a chain), so on each chain the events of round >= r+1 are a suffix of
  those of round >= r. Its first position is found by bisection, eight
  probes per chain and round, in place of a count for every event.
- Fame's vote tallies and the round-received sweep read the la rows of
  the witnesses involved, gathered once per round, in place of la.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import (BIG, FAME_FALSE, FAME_TRUE, FAME_UNDEFINED,
                                 Consensus, _blocks, _fd_rows, _pow2,
                                 _seen_by, _ss_counts, _ss_matrix,
                                 last_ancestors)

AXIS = "ref"
# Elements of one block's compare on each device: bounds memory.
BLOCK_ELEMS = 1 << 26


@partial(jax.jit, static_argnames=("sm",))
def _ss_rows(la, rows, fd_w, w_ok, *, sm):
    """ss[b, w] for the events of `rows` ([blocks, B]) against fd_w."""
    return jax.lax.map(lambda r: _ss_matrix(la[r], fd_w, w_ok, sm=sm), rows)


@jax.jit
def _tally(s, votes):
    """Yays: of the witnesses each row of `s` strongly sees, those that
    vote yes (0/1 matrices; an exact integer product)."""
    return jnp.dot(s.astype(jnp.int8), votes.astype(jnp.int8),
                   preferred_element_type=jnp.int32)


@jax.jit
def _medians(LA, fw, fw_ok, chain, tsr, rows, rr, cr, idx):
    """reference._medians, with the famous witnesses' la rows of each
    round (fw [R, n, n], fw_ok [R, n]) in place of la."""
    n, kp = chain.shape

    def one(r):
        fd = _fd_rows(LA, cr[r], idx[r])  # [B, n]
        g = fw[rr[r][:, None], jnp.arange(n)[None, :], cr[r][:, None]]
        seen = fw_ok[rr[r]] & (g >= idx[r][:, None])
        ev = chain[jnp.arange(n)[None, :], jnp.minimum(fd, kp - 1)]
        rank = jnp.where(seen, tsr[jnp.maximum(ev, 0)], BIG)
        srt = jnp.sort(rank, axis=1)
        k = seen.sum(1) // 2
        return jnp.take_along_axis(srt, k[:, None], axis=1)[:, 0]

    return jax.lax.map(one, rows)


def _pad_rows(a: np.ndarray, rows: int, fill) -> np.ndarray:
    out = np.full((rows,) + a.shape[1:], fill, a.dtype)
    out[: len(a)] = a
    return out


def consensus(n, sp, op, cr, idx, coin, ts, *, devices,
              sm=None) -> Consensus:
    """reference.consensus over a mesh of `devices` (their count must
    divide n)."""
    sp, op, cr, idx = (np.asarray(a, np.int32) for a in (sp, op, cr, idx))
    coin = np.asarray(coin, bool)
    ts = np.asarray(ts, np.int64)
    e = len(sp)
    d = len(devices)
    sm = 2 * n // 3 + 1 if sm is None else sm
    mesh = Mesh(np.array(devices), (AXIS,))
    cols = NamedSharding(mesh, P(None, AXIS))
    split = NamedSharding(mesh, P(AXIS))
    whole = NamedSharding(mesh, P())

    la = last_ancestors(n, sp, op, cr, idx)
    chain_len = np.bincount(cr, minlength=n)
    kp = _pow2(chain_len.max())
    chain = np.full((n, kp), -1, np.int32)
    chain[cr, idx] = np.arange(e, dtype=np.int32)
    la_d = jax.device_put(la, cols)

    def la_block(index):
        ch = chain[index[0]]
        return np.where((ch >= 0)[:, :, None], la[np.maximum(ch, 0)], BIG)

    LA_d = jax.make_array_from_callback((n, kp, n), split, la_block)
    cr_d, idx_d = jax.device_put(cr, whole), jax.device_put(idx, whole)

    def fd_of(ids):
        b = min(_pow2(max(1, BLOCK_ELEMS * d // (n * kp)), 1),
                _pow2(len(ids), 1))
        out = [np.asarray(_fd_rows(LA_d, cr_d[blk], idx_d[blk]))
               for blk in _blocks(ids, b)]
        return np.concatenate(out)[: len(ids)]

    def witnesses(fd_w):
        """fd_w padded to a power of two, split by column, and its mask."""
        wp = _pow2(len(fd_w))
        fw = jax.device_put(_pad_rows(fd_w, wp, BIG), cols)
        return fw, jax.device_put(np.arange(wp) < len(fd_w), whole), wp

    def row_blocks(ids, wp):
        b = min(_pow2(max(1, BLOCK_ELEMS * d // (wp * n)), 1),
                _pow2(len(ids), 1))
        return jnp.asarray(_blocks(np.asarray(ids, np.int32), b))

    def ss_count(ids, wset):
        """Witnesses of `wset` strongly seen by each event of `ids`."""
        fw, ok, wp = wset
        cnt = _ss_counts(la_d, row_blocks(ids, wp), fw, ok, sm=sm)
        return np.asarray(cnt).reshape(-1)[: len(ids)]

    def ss(ids, fd_w):
        """ss[a, w]: event ids[a] strongly sees the witness of fd_w[w]."""
        fw, ok, wp = wset = witnesses(fd_w)
        m = _ss_rows(la_d, row_blocks(ids, wp), fw, ok, sm=sm)
        return np.asarray(m).reshape(-1, wp)[: len(ids), : len(fd_w)]

    # Rounds and witnesses; start[c]: the first chain position of round
    # >= r (chain_len[c] for none).
    rounds = np.zeros(e, np.int32)
    start = np.zeros(n, np.int64)
    wits, fds = [], []
    cand = np.flatnonzero(sp < 0)
    while len(cand):
        fd_c = fd_of(cand)
        jump = ss_count(cand, witnesses(fd_c)) >= sm
        w, fd_w = cand[~jump], fd_c[~jump]
        wits.append(w)
        fds.append(fd_w)
        wset = witnesses(fd_w)
        lo, hi = start.copy(), chain_len.astype(np.int64)
        while (lo < hi).any():
            act = np.flatnonzero(lo < hi)
            mid = (lo[act] + hi[act]) // 2
            up = ss_count(chain[act, mid], wset) >= sm
            hi[act[up]] = mid[up]
            lo[act[~up]] = mid[~up] + 1
        start = lo
        rounds += idx >= start[cr]
        live = np.flatnonzero(start < chain_len)
        cand = np.sort(chain[live, start[live]])
    witness = np.zeros(e, bool)
    for w in wits:
        witness[w] = True
    n_r = len(wits)

    # Fame: votes of round j witnesses on round i witnesses.
    famous = np.zeros((n_r, n), np.int32)
    ss_prev = {}

    def ss_round(j):  # [|W_j|, |W_{j-1}|] strong sight between rounds
        if j not in ss_prev:
            ss_prev[j] = ss(wits[j], fds[j - 1])
        return ss_prev[j]

    for i in range(n_r - 1):
        xs = wits[i]
        fame = np.zeros(len(xs), np.int32)
        votes = (la[wits[i + 1]][:, cr[xs]] >= idx[xs][None, :])
        for j in range(i + 2, n_r):
            s = ss_round(j)
            yays = np.asarray(_tally(s, votes))
            nays = s.sum(1)[:, None] - yays
            v = yays >= nays
            t = np.where(v, yays, nays)
            if (j - i) % n > 0:
                dec = t >= sm
                for k in np.flatnonzero((fame == FAME_UNDEFINED) & dec.any(0)):
                    y = int(np.flatnonzero(dec[:, k])[0])
                    fame[k] = FAME_TRUE if v[y, k] else FAME_FALSE
                votes = v
            else:
                votes = np.where(t >= sm, v, coin[wits[j]][:, None])
            if (fame != FAME_UNDEFINED).all():
                break
        famous[i, cr[xs]] = fame

    # Round received: the first decided round (all earlier rounds decided
    # too) whose famous witnesses see x by more than half.
    decided = [(famous[i, cr[wits[i]]] != FAME_UNDEFINED).all()
               for i in range(n_r)]
    u = decided.index(False) if False in decided else n_r
    rr = np.full(e, -1, np.int32)
    fw_rows = np.full((max(u, 1), n, n), -1, np.int32)
    fw_ok = np.zeros((max(u, 1), n), bool)
    eb = min(_pow2(max(1, BLOCK_ELEMS * d // n), 1), _pow2(e, 1))
    ev_blocks = _blocks(np.arange(e, dtype=np.int32), eb)
    for i in range(1, u):
        f = wits[i][famous[i, cr[wits[i]]] == FAME_TRUE]
        fw_rows[i, cr[f]] = la[f]
        fw_ok[i, cr[f]] = True
        wp = _pow2(len(f), 1)
        rows_f = jax.device_put(_pad_rows(la[f], wp, -1), whole)
        wid = jnp.arange(wp)
        ok = jnp.arange(wp) < len(f)
        seen = np.concatenate([
            np.asarray(_seen_by(rows_f, wid, ok,
                                jax.device_put(cr[blk], split),
                                jax.device_put(idx[blk], split)))
            for blk in ev_blocks])[:e]
        hit = (rr < 0) & (rounds < i) & (seen > len(f) // 2)
        rr[hit] = i

    # Consensus timestamps, as ranks of the timestamps and back.
    cts = np.zeros(e, np.int64)
    got = np.flatnonzero(rr >= 0)
    if len(got):
        tsv, tsr = np.unique(ts, return_inverse=True)
        b = min(_pow2(max(1, BLOCK_ELEMS * d // (n * kp)), 1),
                _pow2(len(got), 1))
        med = np.asarray(_medians(
            LA_d, jax.device_put(fw_rows, whole),
            jax.device_put(fw_ok, whole), jnp.asarray(chain),
            jnp.asarray(tsr.astype(np.int32)),
            jnp.asarray(_blocks(got, b)), jnp.asarray(rr), cr_d,
            idx_d)).reshape(-1)[: len(got)]
        cts[got] = tsv[med]
    return Consensus(rounds, witness, famous, rr, cts)

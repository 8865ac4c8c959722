"""Plain block assembly: the transactions a validator commits, in the
order the Go semantics give them, from the reference's consensus.

Received events (round received >= 0) are ordered by round received,
then consensus timestamp, then the signature's S as an integer: the
upstream ConsensusSorter, whose pseudo-random tiebreak is never set
and always compares equal (hashgraph/consensus_sorter.go:21-52). One
block per round received holds its events' transactions in that order;
a round whose events carry none commits no block. Imports nothing of
babble_tpu."""

from __future__ import annotations

from typing import List, Sequence, Tuple


def order(rr: Sequence[int], cts: Sequence[int],
          s: Sequence[int]) -> List[int]:
    """Indices of the received events, in commit order."""
    got = [i for i in range(len(rr)) if int(rr[i]) >= 0]
    return sorted(got, key=lambda i: (int(rr[i]), int(cts[i]), int(s[i])))


def blocks(rr, cts, s, txs) -> List[Tuple[int, List[bytes]]]:
    """(round received, transactions) of each committed block, in commit
    order; `txs[i]` is event i's transactions (None for none)."""
    out: List[Tuple[int, List[bytes]]] = []
    for i in order(rr, cts, s):
        if not txs[i]:
            continue
        r = int(rr[i])
        if not out or out[-1][0] != r:
            out.append((r, []))
        out[-1][1].extend(txs[i])
    return out

"""HBM bytes the fused kernel's round-received sweep must read in one
dispatch (ops/incremental.py `_consensus_fused`, scope `round_received`),
from its shapes: the roofline share's numerator
(benchmark/metrics/roofline_share.round_received.py).

Each of the `iw` rounds swept compares every lane of the `au`-lane
undecided window against the la rows of the round's witnesses:

- the witness rows, [n, n] int32, on every one of the `devices` that
  holds lanes (each device's lanes reach every witness's column of its
  creators);
- four int32 per lane: creator, chain position, round and the
  round-received carry.

What the compiler rereads beyond that (the gathered [n, lanes] compare
plane is made on chip) is not counted, so the share is a floor on how
close the sweep is to the chip's bandwidth.
"""


def sweep_bytes(au: int, n: int, iw: int, devices: int) -> int:
    return iw * (4 * n * n * devices + 16 * au)

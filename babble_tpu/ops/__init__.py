"""The device consensus engine.

IncrementalEngine (ops/incremental.py) keeps an append-only event DAG
resident in HBM and, for each sync, runs the reference's
DivideRounds / DecideFame / FindOrder (reference
hashgraph/hashgraph.go:616-858) over the undecided tip only, as dense
tensor sweeps in one fused kernel with one packed pull:

- coordinates (lastAncestors, hashgraph.go:448-499): block closure
  matmuls over the new events (ops/closure.py);
- firstDescendants (hashgraph.go:490-530): a resident rank cube kept
  by compare-and-count, since last-ancestor columns are monotone
  along each creator chain;
- rounds and witnesses (hashgraph.go:211-339): a witness-frontier
  sweep, one step per round (ops/frontier.py);
- fame with coin rounds (hashgraph.go:649-730): a vote-matrix
  contraction over voting rounds (ops/kernels.py);
- round received and median timestamps (hashgraph.go:753-799,
  860-868): a windowed sweep and an on-device sort.

Hashing, signatures and the big-int S tiebreak stay on the host; the
device works in int32 event ids. ops/p256.py is the batched signature
check; ops/dag.py and ops/sim.py build DAGs for tests and tools.
"""

from .dag import DagTensors, build_dag, synthetic_dag
from .incremental import IncrementalEngine, RunDelta

__all__ = [
    "DagTensors",
    "build_dag",
    "synthetic_dag",
    "IncrementalEngine",
    "RunDelta",
]

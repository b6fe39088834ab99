"""The plain reference of the placement: which rows each worker holds.

Cyclic placement as the paper defines it (arXiv:2107.09657, Fig. 1b): the
operand's rows are cut into N sub-matrices of equal height, and
sub-matrix g is stored on the J machines g, g+1, ..., g+J-1 (mod N).
Nothing here imports the program; it sees only the configuration's N, J
and row count.

:func:`gaps` holds one step's plan to the configuration's guarantees,
given who was available (from the traffic's own schedule), which workers
the program masked, and, per worker, the rows it computed and the rows
whose copy the combine took from it.
"""

from __future__ import annotations

import numpy as np


def holds(worker: int, rows: int, n_machines: int, replication: int
          ) -> np.ndarray:
    """(rows,) bool: the rows ``worker`` stores under cyclic placement."""
    if rows % n_machines:
        raise ValueError(f"{rows} rows do not split into {n_machines} tiles")
    tile = np.arange(rows) // (rows // n_machines)
    return (worker - tile) % n_machines < replication


def gaps(computed, won, available, masked, n_machines: int,
         replication: int, stragglers: int):
    """(held_rows_gap, coverage_gap, winner_gap) of one step, each a count
    of rows.

    ``computed[n]`` and ``won[n]`` are (rows,) bool per worker n; rows a
    worker computed but does not hold, or computed while away, count in
    the first; rows with fewer than S+1 available computing copies in the
    second; rows without exactly one winner, or whose winner is away or
    masked or did not compute the row, in the third."""
    computed = np.asarray(computed, bool)
    won = np.asarray(won, bool)
    rows = computed.shape[1]
    up = np.zeros(n_machines, bool)
    up[list(available)] = True
    ok = np.zeros(n_machines, bool)
    ok[list(available)] = True
    ok[list(masked)] = False
    held = np.stack([holds(n, rows, n_machines, replication)
                     for n in range(n_machines)])
    held &= up[:, None]
    held_gap = int(np.sum(computed & ~held))
    cover_gap = int(np.sum((computed & up[:, None]).sum(0) < stragglers + 1))
    win_gap = int(np.sum((won.sum(0) != 1)
                         | (won & ~(ok[:, None] & computed)).any(0)))
    return held_gap, cover_gap, win_gap

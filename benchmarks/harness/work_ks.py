"""Bytes the G1 work of one survey's key switch (KeySwitchingPhase) has to
move, from the configuration's sizes alone.

Counted as `work.py` counts: from the algorithm's inputs and outputs, at
its sizes (a point 192 B, a scalar 64 B), so the count is the same whatever
implements the phase. Intermediate points (r Q, x K), window tables, the
running sums and padding are not counted: an implementation that moves them
too reads a smaller share.

  a node's pass  in: V points (the aggregate's K component) and V scalars
                 out: 2 V points (U = r B and W = r Q - x K)

with V the aggregate's length (`queries/<name>.py` `n_values`), and one
pass for every computing node of the roster: the guarantee is a
contribution a node, each with its own secret and scalars, so a program
that made fewer passes would not have moved fewer bytes; it would have
broken the guarantee.
"""
from .work import POINT, SCALAR


def ks_bytes_per_survey(config: dict, v: int) -> int:
    node_pass = v * (POINT + SCALAR + 2 * POINT)
    return int(config["roster"]["n_cns"]) * node_pass

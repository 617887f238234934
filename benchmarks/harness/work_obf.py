"""Bytes the G1 work of one survey's obfuscation phase (ObfuscationPhase)
has to move, from the configuration's sizes alone.

Counted as `work.py` counts: from the algorithm's inputs and outputs, at
its sizes (a ciphertext 384 B, a scalar 64 B), so the count is the same
whatever implements the phase. Intermediate points, window tables and
padding are not counted: an implementation that moves them too reads a
smaller share.

  a node's pass  in: V ciphertexts and V scalars      out: V ciphertexts

with V the aggregate's length (`queries/<name>.py` `n_values`), and one
pass for every computing node of the roster: the guarantee is a pass a
node, each on the one before's output, so a program that made fewer passes
would not have moved fewer bytes; it would have broken the guarantee.
"""
from .work import CIPHERTEXT, SCALAR


def obf_bytes_per_survey(config: dict, v: int) -> int:
    node_pass = v * (CIPHERTEXT + SCALAR + CIPHERTEXT)
    return int(config["roster"]["n_cns"]) * node_pass

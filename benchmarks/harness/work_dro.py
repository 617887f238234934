"""Bytes the G1 work of one survey's noise phase (DROPhase) has to move,
from the configuration's sizes alone.

Counted as `work.py` counts: from the algorithm's inputs and outputs, at
its sizes (a ciphertext 384 B, a scalar 64 B, a plaintext 8 B), so the
count is the same whatever implements the phase. Intermediate points,
window tables, the permutation's indices and padding are not counted: an
implementation that moves them too reads a smaller share.

  noise pass    in: S plaintexts and scalars          out: S ciphertexts
  a node's pass in: S ciphertexts and S scalars       out: S ciphertexts

with S the noise list's size, and one node's pass for every computing node
of the roster (each re-randomises every ciphertext with a fresh encryption
of zero; a pass served from a pool would still read and write the list).
"""
from .work import CIPHERTEXT, PLAIN, SCALAR


def dro_bytes_per_survey(config: dict) -> int:
    size = int(config["diffp"]["noise_list_size"])
    noise_pass = size * (PLAIN + SCALAR + CIPHERTEXT)
    node_pass = size * (CIPHERTEXT + SCALAR + CIPHERTEXT)
    return noise_pass + int(config["roster"]["n_cns"]) * node_pass

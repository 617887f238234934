"""The obfuscation phase: every computing node multiplies the whole
aggregate by secret scalars of its own.

Reference semantics (protocols/obfuscation_protocol.go:241-243, SURVEY.md
row 19): an operation whose answer is a zero / non-zero pattern (min, max,
AND, OR, union, intersection) is run with obfuscation for a querier who may
learn the pattern but not the counts behind it. Each computing node in turn
multiplies BOTH components of EVERY ciphertext by a fresh secret scalar, so
a bucket that held the count c decrypts to (s_1 s_2 ... s_n c) B: the
identity iff c was zero, and a point that says nothing of c otherwise.

THE GUARANTEE, which nothing here or above may weaken: one pass a computing
node, n passes a survey; every pass draws V fresh scalars, one a
ciphertext, that its node alone knows; every pass consumes the previous
node's OUTPUT. The nodes' scalars are never multiplied together into one
product and applied once: in a deployment no party holds two of them, and
with proofs on every node proves its own step on its own input
(proofs/obfuscation.py). `parallel/collective.obfuscate_collective` folds
them because there one mesh program plays every server; the normal path
(LocalCluster.execute_survey, a remote CN's obf_contrib) comes through
`node_pass`, and the counter `obf_scalar_muls` (point multiplications
made: 2 V a pass) says how many a survey made.

One module-level program does a pass's G1 work, `_obf_scalar_mul`, a
`StoredProgram` like the four fused survey programs and the noise phase's
three (utils/exec_store.py; LocalCluster.FUSED names them all): a warm
process on a TPU loads it. It runs at the aggregate's exact width V (its
key holds V alone beside `exec_store.trace_reads`), through
`curve.scalar_mul`: the Pallas variable-base ladder on a TPU, the jnp
ladder elsewhere. A survey that does not obfuscate never calls it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..crypto import curve as C
from ..crypto import elgamal as eg
from ..utils.exec_store import stored
from ..utils.timers import PROCESS, step_of


@stored
@jax.jit
def _obf_scalar_mul(cts, s):
    """One node's pass: out[i] = s[i] * cts[i], both components of the
    ciphertext by its scalar. cts (V, 2, 3, 16), s (V, 16) plain limbs.
    A component a ladder call: V lanes is the shape the decryption runs
    the ladder at, so a process that traces both traces the kernel once,
    and the device keeps these arrays batch-minor, where a component is
    whole rows (the noise phase's finding, PERF.md section 6, PR 31)."""
    return jnp.stack([C.scalar_mul(cts[:, 0], s),
                      C.scalar_mul(cts[:, 1], s)], axis=1)


PROGRAMS = ("_obf_scalar_mul",)


def node_pass(key, cts, tm=None, prove=None):
    """One computing node's pass over the aggregate, as every caller makes
    it (LocalCluster.execute_survey, a remote CN's obf_contrib): V fresh
    scalars drawn from the node's `key`, then every ciphertext of `cts`
    (V, 2, 3, 16), the previous node's output, multiplied by its own.
    Returns (obfuscated cts, the scalars), once the device is done.

    `prove` (proofs on): `prove(k_w, cts, s)` makes the node's proof of
    this step on this input and returns the obfuscated list it proves
    (proofs/obfuscation.create_obfuscation_proofs computes the two
    together), in place of the stored program. `k_w`, the key of the
    proof's blinding w, is split from `key` beside the scalars' own:
    reusing one would make w == s and leak s."""
    V = int(cts.shape[0])
    with step_of(tm, "randomness"):
        k_s, k_w = jax.random.split(key)
        s = jax.block_until_ready(eg.random_scalars(k_s, (V,)))
    with step_of(tm, "mul"):
        out = _obf_scalar_mul(cts, s) if prove is None \
            else prove(k_w, cts, s)
        out = jax.block_until_ready(out)
    PROCESS.count("obf_scalar_muls", 2 * V)
    return out, s


__all__ = ["node_pass", "PROGRAMS"]

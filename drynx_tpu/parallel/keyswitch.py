"""The key switch: every computing node's contribution, one stored pass a
node, and the querier's ciphertext assembled from their sum.

Reference semantics (unlynx key-switching protocol, SURVEY.md §2.2): the
aggregate (K, C) is encrypted under the collective key, the sum of the
computing nodes' public keys. To hand it to the querier, whose public key
is Q, node i draws V fresh scalars r_i and contributes, ciphertext by
ciphertext,

    U_i = r_i B          W_i = r_i Q - x_i K

with x_i its own secret. The switched ciphertext is (sum U_i, C + sum W_i):
the x_i K terms cancel the collective key, the r_i terms re-encrypt under
Q. The contributions commute, so their sum replaces the reference's chain
of nodes; the root adds them in roster order.

THE GUARANTEE, which nothing here or above may weaken: one contribution a
computing node, made with that node's own secret x_i and V scalars r_i
drawn fresh for this survey from that node's own key. The nodes' secrets
are never added into one scalar and applied once: in a deployment no party
holds two of them. What is summed is the contributions, which are public
protocol messages, and with proofs on every node's (U_i, W_i) is proven on
its own (proofs/keyswitch.py). `parallel/collective.keyswitch_contribution`
is the same mathematics for the mesh program that plays every server; the
normal path (LocalCluster.key_switch, a remote CN's ks_contrib) comes
through `node_pass`, and the counter `ks_contributions` (variable-base
multiplications made: V a pass) says how many a survey made.

Two module-level programs do the G1 work, `StoredProgram`s like the fused
survey programs (utils/exec_store.py; LocalCluster.FUSED names them all):
`_ks_pass`, a node's contribution added into the running sums, one
dispatch a node, and `_ks_finish`, once a survey. Both run at the
aggregate's exact width V and their keys hold V alone beside
`exec_store.trace_reads`: no argument, array or program here has a
dimension of the roster's size, so every roster runs the same executables
and a node's pass holds O(V) whatever the roster. The ladders are
`eg.fixed_base_mul` and `curve.scalar_mul` (the Pallas kernels on a TPU,
the jnp ladders elsewhere); the additions go the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..crypto import curve as C
from ..crypto import elgamal as eg
from ..utils.exec_store import stored
from ..utils.timers import PROCESS, step_of


def _add(p, q):
    """p + q, (V, 3, 16) each: on a TPU the Pallas complete-add kernel,
    selected as `eg.fixed_base_mul` selects its ladder; it computes
    `C.add`'s formulas on canonical residues, so the bytes are the jnp
    path's (scripts/pallas_parity.py on the chip). At 48 nodes the jnp
    addition, 2.3 us a point, would be a fifth of the phase."""
    from ..crypto import pallas_ops as po

    if po.available():
        return po.point_add_flat(p, q)
    return C.add(p, q)


@stored
@jax.jit
def _ks_pass(q_tbl, K0, x, r, k_sum, c_sum):
    """One node's pass: U = r B, W = r Q - x K, and the running sums with
    them added. q_tbl the querier's fixed-base table, K0 (V, 3, 16) the
    aggregate's K component, x (16,) the node's secret, r (V, 16) its
    scalars, plain limbs; k_sum, c_sum (V, 3, 16). Returns
    (k_sum + U, c_sum + W, U, W). V lanes is the shape the decryption runs
    the variable-base ladder at, so a process that traces both traces the
    kernel once."""
    u = eg.fixed_base_mul(eg.BASE_TABLE.table, r)
    w = _add(eg.fixed_base_mul(q_tbl, r), C.neg(C.scalar_mul(K0, x)))
    return _add(k_sum, u), _add(c_sum, w), u, w


@stored
@jax.jit
def _ks_finish(agg, k_sum, c_sum, offset_total):
    """The switched ciphertexts (V, 2, 3, 16) from the aggregate and the
    summed contributions: (k_sum, C + c_sum - offset_total B). The last
    term is the public shift of a signed range (n_responders * u^l/2), so
    that decrypted values are the true signed statistics; offset 0 gives
    0 B, the group's identity, so the one program serves both cases."""
    corr = eg.fixed_base_mul(eg.BASE_TABLE.table,
                             eg.int_to_scalar(offset_total[None]))
    c2 = _add(_add(agg[:, 1], c_sum),
              C.neg(jnp.broadcast_to(corr[0], c_sum.shape)))
    return jnp.stack([k_sum, c2], axis=-3)


PROGRAMS = ("_ks_pass", "_ks_finish")


def node_pass(key, K0, x, q_tbl, acc=None, tm=None):
    """One computing node's contribution on the aggregate's K component,
    as every caller makes it (LocalCluster.key_switch, a remote CN's
    ks_contrib): V fresh scalars drawn from the node's `key`, then
    U = r B and W = r Q - x K with the node's secret `x` (16,), added into
    the running sums `acc` = (k_sum, c_sum); None starts them at the
    identity, so the sums that come back are the contribution itself.
    Returns (acc, (U, W, r)), once the device is done: the second is what
    the node's proof is made of (proofs/keyswitch.py) and what a remote
    node sends to the root; with proofs off a local caller drops it."""
    V = int(K0.shape[0])
    with step_of(tm, "randomness"):
        r = jax.block_until_ready(eg.random_scalars(key, (V,)))
    with step_of(tm, "pass"):
        k_sum, c_sum = acc if acc is not None else (C.infinity((V,)),) * 2
        k_sum, c_sum, u, w = jax.block_until_ready(
            _ks_pass(q_tbl, K0, x, r, k_sum, c_sum))
    PROCESS.count("ks_contributions", V)
    return (k_sum, c_sum), (u, w, r)


def finish(agg, acc, offset_total: int = 0, tm=None):
    """The switched ciphertexts from the aggregate (V, 2, 3, 16) and the
    nodes' summed contributions, less the public shift `offset_total` B:
    once a survey."""
    assert 0 <= offset_total < 2 ** 62, "offset too large for int64 scalars"
    with step_of(tm, "finish"):
        return jax.block_until_ready(_ks_finish(
            agg, *acc, jnp.asarray(offset_total, dtype=jnp.int64)))


__all__ = ["node_pass", "finish", "PROGRAMS"]

"""Pool replenishment: compute DRO precompute slabs and deposit them.

This is the crypto half of the pool (store.py stays numpy-only): a
refill step runs ``parallel.dro.precompute_rerandomization`` at the
pool's slab width and deposits the result under the collective-key
digest. The standing server (server/scheduler.py) calls ``refill_slab``
cooperatively on its drain thread — one slab per drain iteration, under
the cluster's proof-device lock, in the encode/verify pipeline gaps —
which is the same pattern its compile lane uses; a caller that fills a
pool ahead of time (tests/test_pool.py) calls ``refill_to``.
"""
from __future__ import annotations

import numpy as np

from . import store as _store


def refill_slab(pool: _store.CryptoPool, key, pub_tbl_table,
                elems: int | None = None) -> str:
    """Compute + deposit ONE slab of fresh zero-encryptions; returns the
    slab id. ``key`` is a jax PRNG key (caller supplies fresh splits —
    the slab's blinding scalars must never repeat); ``pub_tbl_table`` is
    the RAW collective-key fixed-base table (FixedBase.table)."""
    from ..parallel import dro

    elems = int(elems or pool.slab_elems)
    zero_ct, r = dro.precompute_rerandomization(key, pub_tbl_table, elems)
    digest = _store.key_digest(pub_tbl_table)
    return pool.deposit_dro(digest, np.asarray(zero_ct), np.asarray(r))


def refill_to(pool: _store.CryptoPool, key, pub_tbl_table,
              target_elems: int, max_slabs: int | None = None) -> int:
    """Deposit slabs until the balance covers ``target_elems`` (or
    ``max_slabs`` is hit); returns the number of slabs deposited."""
    import jax

    digest = _store.key_digest(pub_tbl_table)
    n = 0
    while pool.dro_balance(digest) < target_elems:
        if max_slabs is not None and n >= max_slabs:
            break
        key, sub = jax.random.split(key)
        refill_slab(pool, sub, pub_tbl_table)
        n += 1
    return n


__all__ = ["refill_slab", "refill_to"]

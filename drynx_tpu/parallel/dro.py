"""Differential privacy: quantized Laplace noise + distributed results
obfuscation (DRO) via re-randomized shuffling.

Reference semantics (SURVEY.md §2.2): the DRO phase builds a list of
encrypted, quantized Laplace noise values; servers shuffle + re-randomize the
list so no one knows which noise value lands on which result; one noise
ciphertext is added per result at the key-switch root
(reference services/service.go:600-604, 619-665; noise list from unlynx
GenerateNoiseValuesScale at service.go:657).

The noise list is DETERMINISTIC (privacy comes from the secret shuffle, not
from sampling): quantized values 0, ±q, ±2q, ... are repeated proportionally
to the Laplace(mean, b) density until `size` values exist.

TPU-first shuffle: each server applies a secret permutation (device PRNG) and
re-randomizes every ciphertext by adding a fresh encryption of zero — the
composition over servers is the reference's Neff-shuffle pipeline's effect.
The shuffle proof itself lives in drynx_tpu.proofs.

Scale (reference TIFS/diffPri.py: noise lists 10k -> 1M, 81.9 -> 5872 s):
the list is device state handled slab by slab from end to end. Three
module-level programs do the phase's G1 work, each on one slab of at most
CHUNK elements: `_dro_noise_enc` (a slab of the noise list encrypted),
`_dro_zero_enc` (a slab of encryptions of zero) and `_dro_permute_add` (a
slab of the permuted list gathered from the whole list and re-randomised).
They are `StoredProgram`s like the four fused survey programs
(utils/exec_store.py): a warm process on a TPU loads them. The scalars and
the permutation are always drawn in ONE call and stay on the device, every
per-element operation is element-wise, so the output is byte-identical
whatever the slab width (one slab of the whole list included;
tests/test_scale_axes.py, tests/test_dro.py). The two encryption programs'
slabs are placed over the proof plane's devices where it has several
(parallel/proof_plane.dispatch_shards); the gather runs where the list
lives, and on a TPU the addition behind it is the Pallas complete-add
kernel (`pallas_ops.point_add_flat`: the same formulas on the same
residues, so the same bytes), traced inside that stored program alone.

API convention: `FixedBase` objects stop at the encryption boundary
(encrypt_noise, dro_pipeline); the shuffle/precompute layer takes raw
(64, 16, 3, 16) limb tables (`FixedBase.table`) and asserts it was not
handed the wrapper — the two used to be silently interchangeable here,
which hid a real type error in dro_pipeline.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import elgamal as eg
from ..resilience.policy import named_lock
from ..utils.exec_store import stored
from ..utils.timers import PROCESS, step_of

# Slab width for chunked precompute / shuffle re-randomization: matches
# the g1 family's max_bucket (crypto/batching.py) and the bucket-grid
# tile (encoding/tiles.py), so slab dispatches land on the same warm
# program sizes.
CHUNK = 4096


def _noise_reps(vs: np.ndarray, mean: float, b: float, quanta: float,
                size: int) -> np.ndarray:
    """Vectorized per-value repetition counts of the density grid.

    np.round matches Python round() (both half-to-even) and np.exp is the
    same libm exp() the scalar loop called — the golden test
    (tests/test_scale_axes.py) pins equality with the reference loop."""
    dens = np.exp(-np.abs(vs - mean) / b)
    return np.maximum(
        1, np.round(dens * size * quanta / (2.0 * b)).astype(np.int64))


def generate_noise_values(size: int, mean: float, b: float, quanta: float,
                          scale: float = 1.0, limit: float = 0.0) -> np.ndarray:
    """Deterministic quantized-Laplace noise list (int64, scaled).

    Mirrors unlynx GenerateNoiseValuesScale as used at reference
    services/service.go:657: values v = mean ± k*quanta, each repeated
    proportionally to exp(-|v-mean|/b); `scale` multiplies values before
    int64 quantization; `limit` (if nonzero) truncates |v| <= limit.

    Vectorized as a NumPy density grid: the interpreted while/extend
    accumulation was O(size) list growth — at the reference's 1M sizes it
    dominated the phase. Output is exactly `_generate_noise_values_ref`'s
    (golden-tested)."""
    if size <= 0:
        return np.zeros((0,), dtype=np.int64)
    total = 0
    vals = np.zeros((0,), dtype=np.float64)
    k_lo, k_hi = 0, 0
    grow = max(64, int(math.isqrt(size)))
    while total < size and k_lo <= 10 * size:
        k_hi = min(k_lo + grow, 10 * size + 1)
        ks = np.arange(k_lo, k_hi, dtype=np.int64)
        # candidate order within the loop: [m] then (m+kq, m-kq) pairs
        vs = np.empty(2 * ks.size, dtype=np.float64)
        vs[0::2] = mean + ks * quanta
        vs[1::2] = mean - ks * quanta
        if k_lo == 0:
            vs = np.concatenate([vs[:1], vs[2:]])  # k=0 contributes once
        if limit:
            vs = vs[np.abs(vs) <= limit]
        if vs.size:
            reps = _noise_reps(vs, mean, b, quanta, size)
            cum = np.cumsum(reps)
            cut = int(np.searchsorted(cum, size - total))
            if cut < vs.size:  # target reached inside this block
                vals = np.concatenate(
                    [vals, np.repeat(vs[:cut + 1], reps[:cut + 1])])
                total += int(cum[cut])
                break
            vals = np.concatenate([vals, np.repeat(vs, reps)])
            total += int(cum[-1])
        k_lo = k_hi
        grow *= 2
    out = vals[:size] * scale
    return np.round(out).astype(np.int64)


def _generate_noise_values_ref(size: int, mean: float, b: float,
                               quanta: float, scale: float = 1.0,
                               limit: float = 0.0) -> np.ndarray:
    """The original interpreted accumulation, kept verbatim as the golden
    reference for the vectorized construction (unit-test only)."""
    if size <= 0:
        return np.zeros((0,), dtype=np.int64)
    vals: list[float] = []
    k = 0
    while len(vals) < size:
        for v in ([mean] if k == 0 else [mean + k * quanta, mean - k * quanta]):
            if limit and abs(v) > limit:
                continue
            dens = math.exp(-abs(v - mean) / b)
            rep = max(1, int(round(dens * size * quanta / (2.0 * b))))
            vals.extend([v] * rep)
            if len(vals) >= size:
                break
        k += 1
        if k > 10 * size:  # safety for degenerate params
            break
    out = np.asarray(vals[:size], dtype=np.float64) * scale
    return np.round(out).astype(np.int64)


def _require_table(tbl, who: str):
    """The shuffle/precompute layer's convention: raw limb tables only."""
    if isinstance(tbl, eg.FixedBase):
        raise TypeError(
            f"{who} takes a raw fixed-base table (FixedBase.table), got a "
            f"FixedBase wrapper — unwrap it at the encryption boundary")
    return tbl


# ---------------------------------------------------------------------------
# The phase's three slab programs. Module-level jits of arrays with the key
# tables as arguments, stored like service._fused_enc/_agg/_ks/_dec
# (LocalCluster.FUSED names them all). The two ladders' programs depend on
# the slab's width alone, so one stored executable serves every list size.
# ---------------------------------------------------------------------------

@stored
@jax.jit
def _dro_noise_enc(base_tbl, pub_tbl, values, r):
    """A slab of signed noise values encrypted: (rB, vB + rP)."""
    return eg.encrypt_ints_with_tables(base_tbl, pub_tbl, values, r)


@stored
@jax.jit
def _dro_zero_enc(base_tbl, pub_tbl, r):
    """A slab of encryptions of zero: (rB, rP). `encrypt_with_tables` on
    zero scalars computes (rB, 0*B + rP); 0*B is the identity and `C.add`
    hands back its other operand untouched when one is the identity, so
    leaving the 0*B ladder out changes no byte (tests/test_dro.py pins it
    on the CPU, benchmarks/check_dro.py on the chip)."""
    return jnp.stack([eg.fixed_base_mul(base_tbl, r),
                      eg.fixed_base_mul(pub_tbl, r)], axis=-3)


@stored
@jax.jit
def _dro_permute_add(cts, idx, zero_ct):
    """A slab of one node's pass: out[i] = cts[idx[i]] + zero_ct[i]. On a
    TPU the slab's 2n points are added by the Pallas complete-add kernel
    (crypto/pallas_ops.py), selected as `eg.fixed_base_mul` selects its
    ladder; it computes `C.add`'s formulas on canonical residues, so the
    bytes are the jnp path's (scripts/pallas_parity.py on the chip)."""
    from ..crypto import pallas_ops as po

    picked = jnp.take(cts, idx, axis=0)
    if po.available():
        # lanes component-major, (n, 2, 3, 16) -> (2, n, 3, 16) -> (2n, 3, 16):
        # the device keeps these arrays batch-minor, so this order moves
        # whole rows where an interleave of the two components would not
        def flat(x):
            return jnp.moveaxis(x, 1, 0).reshape((-1,) + x.shape[2:])

        out = po.point_add_flat(flat(picked), flat(zero_ct))
        return jnp.moveaxis(out.reshape((2, -1) + out.shape[1:]), 0, 1)
    return eg.ct_add(picked, zero_ct)


PROGRAMS = ("_dro_noise_enc", "_dro_zero_enc", "_dro_permute_add")


@functools.partial(jax.jit, static_argnames="n")
def _slab(x, a, n: int):
    """x[a:a+n] with the offset an argument: one small program a list size
    and width, not one a slab."""
    return jax.lax.dynamic_slice_in_dim(x, a, n, axis=0)


def _chunk_of(size: int, chunk) -> int:
    """Effective slab width: None = auto (CHUNK above CHUNK elements),
    0 = force unchunked, positive = forced width."""
    if chunk is None:
        return CHUNK if size > CHUNK else 0
    return int(chunk)


def _slabs_of(size: int, chunk) -> list:
    """[(start, stop)] of the slabs of a list of `size` elements."""
    eff = _chunk_of(size, chunk)
    if not eff or eff >= size:
        return [(0, size)]
    return [(a, min(a + eff, size)) for a in range(0, size, eff)]


def slab_widths(size: int, chunk: int | None = None) -> list[int]:
    """Distinct dispatch widths the slab programs run at for a list of
    ``size`` (at most two: the slab width and a remainder). The
    compilecache registry certifies the three programs at exactly these
    widths (compilecache/registry._pool_specs, Profile.n_noise)."""
    if size <= 0:
        return []
    return sorted({b - a for a, b in _slabs_of(size, chunk)})


def _by_slab(phase: str, chunk, inputs: tuple, program, place: bool):
    """program(*slab of every input) over the slabs of `inputs` (device
    arrays of one length), the outputs in one array. Returns once the
    device is done. `place`: a slab's inputs go to the proof plane's
    device for it where the plane has several."""
    from . import proof_plane as plane

    slabs = _slabs_of(int(inputs[0].shape[0]), chunk)
    if len(slabs) == 1:
        return jax.block_until_ready(program(*inputs))

    def stage(i, a, b):
        cut = tuple(_slab(x, a, b - a) for x in inputs)
        return plane.put_shard(cut, i, donate=True) if place else cut

    parts = plane.dispatch_shards(phase, lambda i, *cut: program(*cut),
                                  slabs, prefetch=stage)
    return jax.block_until_ready(jnp.concatenate(parts, axis=0))


def encrypt_noise(key, pub_table: eg.FixedBase, noise: np.ndarray,
                  chunk: int | None = None, tm=None):
    """Encrypt the noise list under the collective key, slab by slab. The
    scalars are drawn in one call, so the bytes are those of one dispatch
    (`eg.encrypt_ints`) for the same key."""
    if not isinstance(pub_table, eg.FixedBase):
        raise TypeError("encrypt_noise takes the FixedBase wrapper "
                        "(the encryption boundary); got a raw table")
    noise = np.asarray(noise, dtype=np.int64)
    size = int(noise.shape[0])
    with step_of(tm, "noise_enc"):
        values = jnp.asarray(noise)
        PROCESS.count("h2d_bytes", noise.nbytes)
        r = eg.random_scalars(key, (size,))
        base, pub = eg.BASE_TABLE.table, pub_table.table
        cts = _by_slab("DRONoise", chunk, (values, r),
                       lambda v, rs: _dro_noise_enc(base, pub, v, rs),
                       place=True)
    PROCESS.count("dro_encryptions", size)
    return cts


# Builder-invocation counter: increments on every FRESH precompute (the
# expensive fixed-base pass a warm pool exists to skip). The restart test
# (tests/test_pool.py) asserts it stays flat across a simulated restart
# with a warm pool — the pooled path must never fall through to here.
PRECOMPUTE_CALLS = 0
# Scheduler lanes can precompute concurrently; a bare += here would lose
# increments and flake the restart test's stays-flat assertion.
_PRECOMPUTE_COUNT_LOCK = named_lock("precompute_count_lock")


def precompute_rerandomization(key, pub_tbl, size: int, base_tbl=None,
                               chunk: int | None = None, tm=None):
    """Precompute the expensive half of a shuffle step: `size` fresh
    encryptions of zero (r·B, r·P) plus their scalars.

    The reference caches exactly this per server across surveys
    (`pre_compute_multiplications.gob`, services/service.go:34,316-317 +
    unlynx PrecomputationWritingForShuffling) — it is what makes the
    1M-element DRO noise lists survivable. Returns (zero_cts, r) usable as
    the `precomp` argument of shuffle_rerandomize.

    The fixed-base mults run in slabs (`_dro_zero_enc`; byte-identical to
    one dispatch: the scalars r are always drawn in ONE call so chunking
    never changes them). chunk: None = auto, 0 = force monolithic."""
    global PRECOMPUTE_CALLS

    _require_table(pub_tbl, "precompute_rerandomization")
    with _PRECOMPUTE_COUNT_LOCK:
        PRECOMPUTE_CALLS += 1
    base_tbl = base_tbl if base_tbl is not None else eg.BASE_TABLE.table
    with step_of(tm, "zero_enc"):
        r = eg.random_scalars(key, (size,))
        zero_ct = _by_slab("DROPrecompute", chunk, (r,),
                           lambda rs: _dro_zero_enc(base_tbl, pub_tbl, rs),
                           place=True)
    PROCESS.count("dro_encryptions", size)
    return zero_ct, r


def save_precompute(path: str, precomp) -> None:
    """Persist a precomputation (the reference's gob-file equivalent)."""
    zero_ct, r = precomp
    np.savez(path, zero_ct=np.asarray(zero_ct), r=np.asarray(r))


def load_precompute(path: str):
    d = np.load(path)
    return jnp.asarray(d["zero_ct"]), jnp.asarray(d["r"])


def shuffle_rerandomize(key, cts, pub_tbl, base_tbl=None, precomp=None,
                        chunk: int | None = None, tm=None):
    """One server's DRO step: secret permutation + re-randomization.

    cts: (S, 2, 3, 16). Returns (shuffled cts, permutation, rerand scalars)
    — the latter two feed the shuffle proof. `precomp` (from
    precompute_rerandomization, a pool) skips the S fixed-base
    scalar-mults — the hot cost at reference noise sizes (10k..1M,
    TIFS/diffPri.py); without it they are made here, fresh.

    The permutation stays on the device; each slab gathers its part of it
    from the whole list and adds its zero encryptions
    (`_dro_permute_add`). The permutation and blinding scalars are drawn
    identically whatever `chunk` (None = auto above CHUNK, 0 = one slab)
    and the program is element-wise, so the output is byte-identical."""
    _require_table(pub_tbl, "shuffle_rerandomize")
    S = int(cts.shape[0])
    kperm, krand = jax.random.split(key)
    if precomp is None:
        precomp = precompute_rerandomization(krand, pub_tbl, S, base_tbl,
                                             chunk, tm)
    zero_ct, r = precomp
    assert zero_ct.shape[0] == S, (zero_ct.shape, S)
    with step_of(tm, "permute_add"):
        perm = jax.random.permutation(kperm, S)
        out = _by_slab("DROShuffle", chunk, (perm, zero_ct),
                       lambda idx, zc: _dro_permute_add(cts, idx, zc),
                       place=False)
    return out, perm, r


def node_pass(key, cts, pub_tbl, precomp=None, pool=None, digest=None,
              chunk: int | None = None, tm=None):
    """One computing node's pass over the list, as every caller makes it
    (LocalCluster.execute_survey, StreamEngine.advance, a remote CN's
    shuffle_contrib, dro_pipeline): zero encryptions from `precomp` if the
    caller holds some, else consumed from `pool` (a pool.CryptoPool, keyed
    by the collective table's `digest`; strictly once) if it covers the
    list, else made fresh for THIS pass; then permute and add. The
    permutation is drawn from `key` whichever it is. Returns what
    shuffle_rerandomize does."""
    _require_table(pub_tbl, "node_pass")
    S = int(cts.shape[0])
    if precomp is None and pool is not None:
        if digest is None:
            from ..pool import store as _ps

            digest = _ps.key_digest(pub_tbl)
        got = pool.try_consume_dro(digest, S)
        if got is not None:
            precomp = (jnp.asarray(got[0]), jnp.asarray(got[1]))
            PROCESS.count("h2d_bytes", got[0].nbytes + got[1].nbytes)
    return shuffle_rerandomize(key, cts, pub_tbl, precomp=precomp,
                               chunk=chunk, tm=tm)


def pick_add(agg, cts, tm=None):
    """One ciphertext of the shuffled list added to each aggregate
    (reference service.go:600-604): result i takes entry i mod S."""
    from ..crypto import batching as B

    with step_of(tm, "pick_add"):
        idx = np.arange(int(agg.shape[0])) % int(cts.shape[0])
        out = B.ct_add(agg, jnp.take(cts, jnp.asarray(idx), axis=0))
        return jax.block_until_ready(out)


def dro_pipeline(key, pub_tbl: eg.FixedBase, size: int, mean: float,
                 b: float, quanta: float, scale: float = 1.0,
                 limit: float = 0.0, n_servers: int = 3,
                 chunk: int | None = None, pool=None):
    """Full noise phase: generate, encrypt, pass through every server's
    shuffle+rerandomize. Returns the final encrypted noise list.

    ``pool`` (a pool.CryptoPool): each server pass first tries to consume
    ``size`` precomputed zero-encryptions keyed by this public table's
    digest — the reference's gob-cache economics (precompute dominates at
    10k..1M noise sizes; a warm pool leaves only permute+add). A short
    pool falls back to fresh precompute for THAT pass only. Consumption
    is strictly once (pool/store.py); the permutation is drawn from the
    pipeline key either way, so pooled output decrypts identically to the
    fresh-randomness path (tests/test_pool.py pins it)."""
    if not isinstance(pub_tbl, eg.FixedBase):
        raise TypeError("dro_pipeline takes the FixedBase wrapper; pass "
                        "pub_tbl.table only to the shuffle layer")
    noise = generate_noise_values(size, mean, b, quanta, scale, limit)
    key, sub = jax.random.split(key)
    cts = encrypt_noise(sub, pub_tbl, noise, chunk=chunk)
    for _ in range(n_servers):
        key, sub = jax.random.split(key)
        cts, _, _ = node_pass(sub, cts, pub_tbl.table, pool=pool,
                              chunk=chunk)
    return cts, noise


__all__ = ["generate_noise_values", "encrypt_noise", "shuffle_rerandomize",
           "precompute_rerandomization", "save_precompute", "load_precompute",
           "node_pass", "pick_add", "dro_pipeline", "slab_widths", "CHUNK",
           "PROGRAMS"]

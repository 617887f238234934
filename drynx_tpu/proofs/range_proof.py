"""Batched CCS-style ZK range proofs with Boneh–Boyen digit signatures.

Reference semantics (lib/range/range_proof.go): a DP proves its ElGamal
plaintext σ ∈ [0, u^l) by base-u digit decomposition (ToBase :584). Each CN
publishes BB signatures A[k] = (x+k)^{-1}·B2 for k<u (InitRangeProofSignature
:270-288); the proof blinds the digit signatures (V = v·A[φ] :392-394),
commits D = Σ u^j s_j·B + m·P, and answers challenge c with Zphi, Zv, Zr;
the verifier checks
  D  == c·C + Zr·P + Σ u^j·Zphi_j·B                       (:519-529)
  a  == e(c·y − Zphi_j·B, V_ij) · e(B,B2)^{Zv_ij}         (:538-546)
(the reference's three pairings per digit collapse to ONE pairing + one GT
exponentiation here — same equation, shared bilinearity).

Fiat-Shamir binding: the reference hashes only c = sha3-512(B ‖ C ‖ ΣY)
(:348-375) and its verifier trusts the transmitted challenge — so a forger
can fix c FIRST, choose Zphi/Zr/Zv/V freely, and *derive* D and a from the
two verifier equations; every check passes for a ciphertext encrypting
anything. This implementation closes that hole: the challenge is
  c = sha3-512(B ‖ C2 ‖ ΣY ‖ u ‖ l ‖ D ‖ V_pts ‖ a)
i.e. it binds ALL prover commitments (proper sigma-protocol Fiat-Shamir:
commit, then hash, then respond), and verification REQUIRES the recomputed
challenge to match. Deriving D or a post-hoc now changes c, which changes
the equations they must satisfy — a hash-fixed-point search.

TPU design: one proof BATCH covers a whole ciphertext vector (V values):
digits, responses and blinded signatures are (ns, V, l, ...) limb tensors;
the pairings run as one batched Miller-loop scan. Host work is only the
Fiat-Shamir hash.
"""
from __future__ import annotations

import dataclasses
import secrets
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import curve as C
from ..crypto import elgamal as eg
from ..crypto import field as F
from ..crypto import fp12 as F12
from ..crypto import g2 as G2
from ..crypto import pairing as PAIR
from ..resilience.policy import named_lock
from ..crypto import params, refimpl
from ..crypto.field import FN, FP
from . import encoding as enc

# ---------------------------------------------------------------------------
# Signature initialization (per CN, host-side — rare, key-lifetime event)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RangeSig:
    """One server's digit-signature set for base u (PublishSignature)."""

    secret: int
    public: tuple           # host affine G1 ints (y = x·B)
    A: np.ndarray           # (u, 3, 2, 16) G2 Jacobian Montgomery limbs
    gt: Optional[np.ndarray] = None   # (u, 6, 2, 16) e(B, A[k]) cache

    @property
    def u(self) -> int:
        return self.A.shape[0]


def sig_gt_table(sigs: list["RangeSig"]) -> jnp.ndarray:
    """(ns, u, 6, 2, 16): gtA[i][k] = e(B, A_i[k]), computed once per
    signature set (u*ns pairings) and cached on the RangeSig objects.

    This is the prover-side shortcut the fixed digit-signature structure
    allows: a_ij = e(-s_j B, v_ij A_i[phi_j]) * gtB^t = gtA[i][phi_j]^(-s_j
    v_ij) * gtB^t — one GT exponentiation instead of a Miller loop + final
    exp per digit (the reference pairs every element,
    range_proof.go:396-404)."""
    from ..crypto import batching as B

    # module-level LRU keyed by a digest of the A-table bytes: the TCP path
    # rebuilds RangeSig objects from the wire for every survey, so
    # instance-level caching alone would recompute the "one-time" table
    # each survey. Bounded + hashed keys so a long-lived node serving many
    # signature sets doesn't grow without limit.
    import hashlib

    def _key(sg):
        return hashlib.sha256(sg.A.tobytes()).digest()

    for sg in sigs:
        if sg.gt is None:
            hit = _GT_TABLE_CACHE.pop(_key(sg), None)
            if hit is not None:
                _GT_TABLE_CACHE[_key(sg)] = hit   # refresh LRU order
                sg.gt = hit

    # second chance behind the LRU: the persistent sig-table store (the
    # active crypto pool) — a fresh process against known signatures
    # reloads instead of re-pairing (same digest key as the LRU)
    store = _sig_store()
    if store is not None:
        for sg in sigs:
            if sg.gt is None:
                d = store.load_sig("gt", _key(sg).hex())
                if d is not None:
                    sg.gt = d["gt"]
                    _GT_TABLE_CACHE[_key(sg)] = sg.gt

    missing = [sg for sg in sigs if sg.gt is None]
    if missing:
        with _SIG_COUNT_LOCK:
            SIG_BUILD_COUNTS["gt_table"] += 1
        A_all = jnp.asarray(np.stack([sg.A for sg in missing]), dtype=jnp.uint32)
        qx, qy, _ = B.g2_normalize(A_all)
        bx = jnp.asarray(F.to_mont(jnp.asarray(
            F.from_int(params.G1_GEN[0]), dtype=jnp.uint32), FP), dtype=jnp.uint32)
        by = jnp.asarray(F.to_mont(jnp.asarray(
            F.from_int(params.G1_GEN[1]), dtype=jnp.uint32), FP), dtype=jnp.uint32)
        gt = np.asarray(B.pair(bx, by, qx, qy))
        for i, sg in enumerate(missing):
            sg.gt = gt[i]
            _GT_TABLE_CACHE[_key(sg)] = gt[i]
            if store is not None:
                store.save_sig("gt", _key(sg).hex(), gt=np.asarray(gt[i]))
        while len(_GT_TABLE_CACHE) > _GT_TABLE_CACHE_MAX:
            _GT_TABLE_CACHE.pop(next(iter(_GT_TABLE_CACHE)))
    return jnp.asarray(np.stack([sg.gt for sg in sigs]), dtype=jnp.uint32)


_GT_TABLE_CACHE: dict = {}
_GT_TABLE_CACHE_MAX = 32

_GT_POW_TABLE_CACHE: dict = {}
_GT_POW_TABLE_MAX = 4           # ~38 MB each at ns=3, u=16

# Builder-invocation counters: bumped only by REAL builds (the pairing
# batch / the ~10 s host pow-table loop), never by LRU or store hits.
# The restart test (tests/test_pool.py) asserts they stay flat when a
# fresh process reloads from the persistent sig-table store.
SIG_BUILD_COUNTS = {"gt_table": 0, "pow_table": 0}
# Verify workers build sig tables concurrently; dict += is read-modify-
# write, so the counters are bumped under a named lock.
_SIG_COUNT_LOCK = named_lock("sig_count_lock")


def _sig_store():
    """The persistent sig-table store, if a crypto pool is active
    (content-addressed by A-table digest — safe to share process-wide)."""
    from .. import pool as pool_mod

    return pool_mod.active_pool()


def sig_gt_pow_tables(sigs: list["RangeSig"]) -> np.ndarray:
    """(ns*u, 64, 16, 6, 2, 16): 4-bit window tables of every digit-signature
    GT base gtA[i][k] = e(B, A_i[k]), flattened base-major (i*u + k).

    With these, creation's dominant kernel — gtA[i][phi]^(-s v) over every
    digit — becomes a gather + two mulreduce8 passes (63 GT muls, ZERO
    squarings), vs ~258 squarings + 86 muls for the windowed ladder. The
    build runs on the HOST oracle (~10 s for ns=3, u=16) once per signature
    set and is LRU-cached by the A-table digest, so every survey against
    the same signatures reuses it (same pattern as sig_gt_table)."""
    import hashlib

    from ..crypto import host_oracle as ho

    key = hashlib.sha256(b"".join(sg.A.tobytes() for sg in sigs)).digest()
    hit = _GT_POW_TABLE_CACHE.pop(key, None)
    if hit is not None:
        _GT_POW_TABLE_CACHE[key] = hit          # refresh LRU order
        return hit

    store = _sig_store()
    if store is not None:
        d = store.load_sig("pow", key.hex())
        if d is not None:
            T = d["T"]
            _GT_POW_TABLE_CACHE[key] = T
            while len(_GT_POW_TABLE_CACHE) > _GT_POW_TABLE_MAX:
                _GT_POW_TABLE_CACHE.pop(next(iter(_GT_POW_TABLE_CACHE)))
            return T

    with _SIG_COUNT_LOCK:
        SIG_BUILD_COUNTS["pow_table"] += 1
    gtA = np.asarray(sig_gt_table(sigs))        # (ns, u, 6, 2, 16)
    ns, u = gtA.shape[0], gtA.shape[1]
    T = np.empty((ns * u, 64, 16, 6, 2, 16), np.uint32)
    for b in range(ns * u):
        cur = ho._fp12_to_ref(gtA[b // u, b % u])
        for w in range(64):
            row = refimpl.FP12_ONE
            T[b, w, 0] = ho._fp12_from_ref(row)
            for j in range(1, 16):
                row = refimpl.fp12_mul(row, cur)
                T[b, w, j] = ho._fp12_from_ref(row)
            for _ in range(4):
                cur = refimpl.fp12_sq(cur)
    _GT_POW_TABLE_CACHE[key] = T                # host numpy (tracer safety)
    if store is not None:
        store.save_sig("pow", key.hex(), T=T)
    while len(_GT_POW_TABLE_CACHE) > _GT_POW_TABLE_MAX:
        _GT_POW_TABLE_CACHE.pop(next(iter(_GT_POW_TABLE_CACHE)))
    return T


_GT_POW_TABLE_DEV: dict = {}


def _sig_gt_pow_tables_dev(sigs: list["RangeSig"]) -> jnp.ndarray:
    """Device copy of sig_gt_pow_tables, memoized by the same digest so the
    ~38 MB table is uploaded ONCE per signature set, not per creation call.
    Safe to cache: created eagerly (outside any trace), so it is a concrete
    Array, not a tracer."""
    import hashlib

    key = hashlib.sha256(b"".join(sg.A.tobytes() for sg in sigs)).digest()
    dev = _GT_POW_TABLE_DEV.get(key)
    if dev is None:
        dev = jnp.asarray(sig_gt_pow_tables(sigs), dtype=jnp.uint32)
        _GT_POW_TABLE_DEV[key] = dev
        while len(_GT_POW_TABLE_DEV) > _GT_POW_TABLE_MAX:
            _GT_POW_TABLE_DEV.pop(next(iter(_GT_POW_TABLE_DEV)))
    return dev


_GT_POW_MULTI = None


def _gt_pow_multi(tables, base_idx, k):
    """Bucketed gt_pow_fixed_multi (TPU path only — callers gate)."""
    from ..crypto import batching as B
    from ..crypto import pallas_pairing as pp

    global _GT_POW_MULTI
    if _GT_POW_MULTI is None:
        _GT_POW_MULTI = B.bucketed(pp.gt_pow_fixed_multi, (-1, 0, 1), 3,
                                   min_bucket=32, max_bucket=2048,
                                   name="gt_pow_fixed_multi")
    return _GT_POW_MULTI(tables, base_idx, k)


def init_range_sig(u: int, rng: np.random.Generator) -> RangeSig:
    """BB signatures A[k] = (x+k)^{-1}·B2, k in [0, u)
    (reference InitRangeProofSignature, range_proof.go:270-288)."""
    x, pub = eg.keygen(rng)
    pts = []
    for k in range(u):
        inv = pow((x + k) % params.N, params.N - 2, params.N)
        pts.append(G2.from_ref(refimpl.g2_mul(refimpl.G2, inv)))
    return RangeSig(secret=x, public=pub, A=np.stack(pts))


def to_base(n, b: int, l: int) -> np.ndarray:
    """Base-b digits, little-endian, padded to l (reference ToBase :584)."""
    n = np.asarray(n, dtype=np.int64)
    digits = np.zeros(n.shape + (l,), dtype=np.int32)
    cur = n.copy()
    for j in range(l):
        digits[..., j] = cur % b
        cur //= b
    return digits


# ---------------------------------------------------------------------------
# Proof container
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RangeProofBatch:
    """Proofs for V values against ns servers, base u, l digits.

    Mirrors RangeProofData (range_proof.go:32-39) with the value axis
    batched: Challenge->challenge, Zr->zr, D->d, Zphi->zphi, Zv->zv, V->v_pts,
    A->a.
    """

    commit: jnp.ndarray      # (V, 2, 3, 16) the ciphertexts themselves
    challenge: jnp.ndarray   # (V, 16)
    zr: jnp.ndarray          # (V, 16)
    d: jnp.ndarray           # (V, 3, 16)
    zphi: jnp.ndarray        # (V, l, 16)
    zv: jnp.ndarray          # (ns, V, l, 16)
    v_pts: jnp.ndarray       # (ns, V, l, 3, 2, 16)
    a: jnp.ndarray           # (ns, V, l, 6, 2, 16)
    u: int
    l: int
    # canonical-byte cache for the Fiat-Shamir transcript + serialization:
    # {'commit': (V,128), 'd': (V,64), 'v': (ns,V,l,128), 'a': (ns,V,l,384)}
    # uint8 numpy. Filled at creation (the bytes ARE the wire format) and at
    # from_bytes (the received wire bytes) so neither side pays a second
    # normalize/from_mont device pass to re-derive them. Hashing the wire
    # bytes is the standard FS practice (bind the message as transmitted):
    # decode(bytes) -> point is deterministic, so binding the bytes binds
    # the commitments at least as strongly as re-encoding would.
    # INVARIANT: when set, `wire` MUST be the canonical encoding of the
    # tensors above. create_range_proofs and from_bytes maintain this; any
    # code building a MODIFIED batch (e.g. dataclasses.replace in tests)
    # must pass wire=None so verification re-derives the bytes — a stale
    # cache would make the challenge binding vacuous for that object (the
    # wire attack surface itself cannot diverge: from_bytes decodes tensors
    # and cache from the same buffer).
    wire: Optional[dict] = None

    @property
    def n_values(self) -> int:
        return int(self.commit.shape[0])

    @property
    def n_servers(self) -> int:
        return int(self.zv.shape[0])

    def wire_bytes(self) -> dict:
        """The canonical commitment bytes (compute-if-missing)."""
        if self.wire is None:
            self.wire = _range_wire_dict(self.commit, self.d, self.v_pts,
                                         self.a)
        return self.wire

    def to_bytes(self) -> bytes:
        """Canonical serialization (RangeProof.ToBytes, :92-146)."""
        head = np.asarray([self.u, self.l, self.n_values, self.n_servers],
                          dtype="<i8").tobytes()
        w = self.wire_bytes()
        parts = [
            w["commit"], enc.scalar_bytes(self.challenge),
            enc.scalar_bytes(self.zr), w["d"],
            enc.scalar_bytes(self.zphi), enc.scalar_bytes(self.zv),
            w["v"], w["a"],
        ]
        return head + b"".join(np.ascontiguousarray(p).tobytes()
                               for p in parts)

    @classmethod
    def from_bytes(cls, buf: bytes) -> "RangeProofBatch":
        u, l, V, ns = np.frombuffer(buf[:32], dtype="<i8")
        u, l, V, ns = int(u), int(l), int(V), int(ns)
        off = 32

        def take(shape, nbytes):
            nonlocal off
            flat = np.frombuffer(buf[off:off + nbytes], dtype=np.uint8)
            off += nbytes
            return flat.reshape(shape)

        commit_b = take((V, 2, 64), V * 128)
        commit = _g1_from_bytes(commit_b).reshape(V, 2, 3, params.NUM_LIMBS)
        challenge = enc.bytes_to_limbs(take((V, 32), V * 32))
        zr = enc.bytes_to_limbs(take((V, 32), V * 32))
        d_b = take((V, 64), V * 64)
        d = _g1_from_bytes(d_b)
        zphi = enc.bytes_to_limbs(take((V, l, 32), V * l * 32))
        zv = enc.bytes_to_limbs(take((ns, V, l, 32), ns * V * l * 32))
        v_b = take((ns, V, l, 128), ns * V * l * 128)
        v_pts = _g2_from_bytes(v_b)
        a_b = take((ns, V, l, 384), ns * V * l * 384)
        a = _gt_from_bytes(a_b)
        wire = {"commit": commit_b.reshape(V, 128).copy(), "d": d_b.copy(),
                "v": v_b.copy(), "a": a_b.copy()}
        return cls(jnp.asarray(commit, dtype=jnp.uint32), jnp.asarray(challenge, dtype=jnp.uint32),
                   jnp.asarray(zr, dtype=jnp.uint32), jnp.asarray(d, dtype=jnp.uint32), jnp.asarray(zphi, dtype=jnp.uint32),
                   jnp.asarray(zv, dtype=jnp.uint32), jnp.asarray(v_pts, dtype=jnp.uint32), jnp.asarray(a, dtype=jnp.uint32), u, l,
                   wire=wire)


def _g1_from_bytes(b: np.ndarray) -> np.ndarray:
    """(..., 64) canonical bytes -> (..., 3, 16) Jacobian Montgomery."""
    from ..crypto import batching as B

    x = enc.bytes_to_limbs(b[..., :32])
    y = enc.bytes_to_limbs(b[..., 32:])
    inf = np.all(b == 0, axis=-1)
    xm = np.asarray(B.to_mont_p(jnp.asarray(x, dtype=jnp.uint32)))
    ym = np.asarray(B.to_mont_p(jnp.asarray(y, dtype=jnp.uint32)))
    one = np.broadcast_to(np.asarray(FP.one_mont), xm.shape).copy()
    one[inf] = 0
    ym = ym.copy()
    ym[inf] = np.asarray(FP.one_mont)  # match infinity() convention (z=0)
    xm = xm.copy()
    xm[inf] = np.asarray(FP.one_mont)
    return np.stack([xm, ym, one], axis=-2)


def _g2_from_bytes(b: np.ndarray) -> np.ndarray:
    """(..., 128) -> (..., 3, 2, 16) Jacobian Montgomery."""
    from ..crypto import batching as B

    comps = [enc.bytes_to_limbs(b[..., 32 * k:32 * (k + 1)]) for k in range(4)]
    inf = np.all(b == 0, axis=-1)
    xm = np.stack([np.asarray(B.to_mont_p(jnp.asarray(c, dtype=jnp.uint32)))
                   for c in comps[:2]], axis=-2)
    ym = np.stack([np.asarray(B.to_mont_p(jnp.asarray(c, dtype=jnp.uint32)))
                   for c in comps[2:]], axis=-2)
    zm = np.zeros_like(xm)
    zm[..., 0, :] = np.asarray(FP.one_mont)
    zm[inf] = 0
    # infinity convention from g2.from_ref: x=y=(1,0) Montgomery, z=0
    one_fp2 = np.zeros_like(xm[inf])
    if one_fp2.size:
        one_fp2[..., 0, :] = np.asarray(FP.one_mont)
        xm[inf] = one_fp2
        ym[inf] = one_fp2
    return np.stack([xm, ym, zm], axis=-3)


def _gt_from_bytes(b: np.ndarray) -> np.ndarray:
    """(..., 384) -> (..., 6, 2, 16) Montgomery."""
    from ..crypto import batching as B

    limbs = enc.bytes_to_limbs(b.reshape(b.shape[:-1] + (12, 32)))
    return np.asarray(B.to_mont_p(jnp.asarray(limbs, dtype=jnp.uint32))).reshape(
        b.shape[:-1] + (6, 2, params.NUM_LIMBS))


# ---------------------------------------------------------------------------
# Shared constants
# ---------------------------------------------------------------------------

_GT_B = None


def gt_base():
    """e(B, B2) — the pairing of both generators, device constant.

    Memoized as HOST numpy (a jnp value cached from inside a jit trace
    would be a leaked tracer — see pairing._twist_frob_consts)."""
    global _GT_B
    if _GT_B is None:
        _GT_B = np.asarray(F12.from_ref(refimpl.pair(refimpl.G1,
                                                     refimpl.G2)))
    return jnp.asarray(_GT_B, dtype=jnp.uint32)


_GT_B_TABLE = None
_GT_POW_GTB = None


def gt_base_table() -> jnp.ndarray:
    """4-bit window table of gtB powers: T[w][j] = gtB^(j * 16^w),
    (64, 16, 6, 2, 16). One-time host build (~1.2k oracle Fp12 muls),
    cached for the process; lets every gtB^k collapse to 63 GT muls
    (pallas_pairing.gt_pow_fixed) with no squarings."""
    global _GT_B_TABLE
    if _GT_B_TABLE is None:
        base = refimpl.pair(refimpl.G1, refimpl.G2)
        T = np.empty((64, 16, 6, 2, 16), np.uint32)
        cur = base
        for w in range(64):
            row = refimpl.FP12_ONE
            T[w, 0] = F12.from_ref(row)
            for j in range(1, 16):
                row = refimpl.fp12_mul(row, cur)
                T[w, j] = F12.from_ref(row)
            for _ in range(4):
                cur = refimpl.fp12_mul(cur, cur)
        _GT_B_TABLE = T  # host numpy; converted per use (tracer safety)
    return jnp.asarray(_GT_B_TABLE, dtype=jnp.uint32)


def gt_pow_gtb(k):
    """gtB^k batched over any leading shape of k (..., 16) plain limbs."""
    from ..crypto import batching as B
    from ..crypto import pallas_ops as po
    from ..crypto import pallas_pairing as pp

    if not po.available():
        return B.gt_pow(gt_base(), k)
    global _GT_POW_GTB
    if _GT_POW_GTB is None:
        tab = gt_base_table()
        _GT_POW_GTB = B.bucketed(
            lambda kk: pp.gt_pow_fixed(tab, kk), (1,), 3, min_bucket=32,
            max_bucket=2048, name="gt_pow_gtb")
    return _GT_POW_GTB(k)


def aot_register_bucketed(build_gtb_table: bool = False) -> None:
    """Force-build the LAZY bucketed wrappers so BUCKETED_OPS enumerates
    them (the precompile registry, drynx_tpu/compilecache). Both wrappers
    are memoized module globals, so the runtime paths above reuse the
    exact objects registered here — no duplicate traces.

    build_gtb_table: also build gt_pow_gtb, whose closure captures the
    gtB window table (a ~1.2k-mul HOST build) — only worth paying when
    the Pallas path will actually dispatch it (it is TPU-only)."""
    from ..crypto import batching as B
    from ..crypto import pallas_pairing as pp

    global _GT_POW_MULTI, _GT_POW_GTB
    if _GT_POW_MULTI is None:
        _GT_POW_MULTI = B.bucketed(pp.gt_pow_fixed_multi, (-1, 0, 1), 3,
                                   min_bucket=32, max_bucket=2048,
                                   name="gt_pow_fixed_multi")
    if build_gtb_table and _GT_POW_GTB is None:
        tab = gt_base_table()
        _GT_POW_GTB = B.bucketed(
            lambda kk: pp.gt_pow_fixed(tab, kk), (1,), 3, min_bucket=32,
            max_bucket=2048, name="gt_pow_gtb")


def prewarm_sig_tables(sigs: list["RangeSig"],
                       pow_tables: bool | None = None) -> None:
    """Build the per-signature GT tables OUTSIDE the timed survey path.

    sig_gt_table (one pairing batch) and — on the Pallas path —
    sig_gt_pow_tables (~10 s host build at ns=3, u=16) used to be built
    lazily inside create_range_proofs, landing their one-time cost in the
    middle of the timed proofs window. Both are LRU-cached by the A-table
    digest, so calling this at signature setup (LocalCluster
    ensure_range_sigs) makes the in-survey lookups pure cache hits."""
    from ..crypto import pallas_ops as po

    sig_gt_table(sigs)
    if pow_tables is None:
        pow_tables = po.available()
    if pow_tables:
        _sig_gt_pow_tables_dev(sigs)


def _upow_mont(u: int, l: int) -> jnp.ndarray:
    """[u^j mod n for j<l] in Montgomery form, (l, 16)."""
    rows = [F.from_int((pow(u, j, params.N) * params.R) % params.N)
            for j in range(l)]
    return jnp.asarray(np.stack(rows), dtype=jnp.uint32)


def _weighted_sum_mod_n(s_plain, upow_m):
    """Σ_j u^j · s_j mod n. s_plain (..., l, 16), upow_m (l, 16) Montgomery."""
    from ..crypto import batching as B

    prod = B.fn_mont_mul(s_plain, upow_m)  # plain·mont = plain product
    acc = prod[..., 0, :]
    for j in range(1, prod.shape[-2]):
        acc = B.fn_add(acc, prod[..., j, :])
    return acc


_BASE_B = None


def _g1_gen_bytes() -> np.ndarray:
    """Canonical bytes of the G1 generator — pure host, memoized (this used
    to be a device normalize dispatch on EVERY challenge computation)."""
    global _BASE_B
    if _BASE_B is None:
        _BASE_B = _g1_bytes_host(refimpl.G1)
    return _BASE_B


def _range_wire_dict(commit, d, v_pts, a) -> dict:
    """THE one definition of the canonical commitment encoding — creation,
    wire_bytes and the device-tensor challenge path all call this so the
    Fiat-Shamir transcript can never desynchronize between them."""
    return {"commit": enc.ct_bytes(jnp.asarray(commit, dtype=jnp.uint32)),
            "d": enc.g1_bytes(jnp.asarray(d, dtype=jnp.uint32)),
            "v": enc.g2_bytes(jnp.asarray(v_pts, dtype=jnp.uint32)),
            "a": enc.gt_bytes(jnp.asarray(a, dtype=jnp.uint32))}


def _g1_bytes_host(pt) -> np.ndarray:
    """Canonical 64-byte encoding of a host affine int pair (no device);
    None (infinity) encodes all-zero, matching enc.g1_bytes."""
    if pt is None:
        return np.zeros(64, dtype=np.uint8)
    x, y = int(pt[0]), int(pt[1])
    return np.frombuffer(x.to_bytes(32, "big") + y.to_bytes(32, "big"),
                         dtype=np.uint8)


def challenge_from_wire(wire: dict, sum_y_bytes: np.ndarray,
                        u: int, l: int) -> np.ndarray:
    """Per-value Fiat-Shamir challenge from the CANONICAL WIRE BYTES:

      c = sha3-512(B ‖ C2 ‖ ΣY ‖ u ‖ l ‖ D ‖ V_pts[·,v,·] ‖ a[·,v,·])

    The reference hashes only (B ‖ commit ‖ ΣY) (range_proof.go:348-375),
    which lets a forger derive D and a AFTER fixing c (see module
    docstring). Binding D, the blinded signatures V and the pairing
    commitments a makes the transcript a proper sigma-protocol
    Fiat-Shamir transform. Pure host work: byte slicing + sha3.
    """
    # explicit little-endian so the transcript is canonical across hosts
    # (all other hashed inputs go through explicit byte encoders)
    ul = np.frombuffer(np.asarray([u, l], dtype="<i8").tobytes(),
                       dtype=np.uint8)
    V = wire["commit"].shape[0]
    c2 = wire["commit"].reshape(V, 128)[:, 64:]              # (V, 64)
    d_b = wire["d"]                                          # (V, 64)
    v_b = np.moveaxis(wire["v"], 0, 1)
    v_b = np.ascontiguousarray(v_b).reshape(V, -1)           # (V, ns*l*128)
    a_b = np.moveaxis(wire["a"], 0, 1)
    a_b = np.ascontiguousarray(a_b).reshape(V, -1)           # (V, ns*l*384)
    return enc.hash_to_scalar(_g1_gen_bytes(), c2, sum_y_bytes, ul, d_b,
                              v_b, a_b, batch_shape=(V,))


def proof_challenge(cts, sum_y_bytes: np.ndarray, d, v_pts, a,
                    u: int, l: int) -> np.ndarray:
    """Challenge from DEVICE tensors: canonicalizes to bytes, then hashes
    (see challenge_from_wire). Kept for callers without a byte cache."""
    return challenge_from_wire(_range_wire_dict(cts, d, v_pts, a),
                               sum_y_bytes, u, l)


def sum_publics_bytes(sigs: list[RangeSig]) -> np.ndarray:
    acc = None
    for s in sigs:
        acc = refimpl.g1_add(acc, s.public)
    return _g1_bytes_host(acc)


# ---------------------------------------------------------------------------
# Creation
# ---------------------------------------------------------------------------


def _commit_kernel(digits, s, t, m, v, A_tab, ca_tbl, u: int, l: int,
                   gtA=None, gtA_pow=None):
    """Commitment stage of proof creation (independent of the challenge),
    built from bucketed primitives (each compiles once per size bucket —
    see crypto/batching.py).

    digits (V, l) int32; s, t, m (V, l, 16); v (ns, V, l, 16);
    A_tab (ns, u, 3, 2, 16); ca_tbl: collective-key fixed-base table.
    Returns D (V, 3, 16), m_tot (V, 16), V_pts, a.
    """
    from ..crypto import batching as B
    from ..crypto import pallas_ops as po

    # On the Pallas path every stage waits for the device before the next
    # is enqueued; elsewhere this is a no-op. The sync was written for an
    # accelerator arrangement that is gone, and whether a local chip needs
    # it is unverified: removing it is a perf_opt issue's, with a cell to
    # judge it.
    sync = jax.block_until_ready if po.available() else (lambda x: x)

    base_tbl = eg.BASE_TABLE.table
    upow_m = _upow_mont(u, l)

    # D = (Σ u^j s_j)·B + (Σ m_j)·P
    w = _weighted_sum_mod_n(s, upow_m)
    m_tot = m[..., 0, :]
    for j in range(1, l):
        m_tot = B.fn_add(m_tot, m[..., j, :])
    D = B.g1_add(B.fixed_base_mul(base_tbl, w),
                 B.fixed_base_mul(ca_tbl, m_tot))
    sync(D)

    # V_ij = v_ij · A_i[φ_j]  — gather digit signatures, blind in G2
    A_sel = A_tab[:, digits]                               # (ns, V, l, 3, 2, 16)
    V_pts = B.g2_scalar_mul(A_sel, v)
    sync(V_pts)

    # a_ij = e(−s_j·B, V_ij) · gtB^{t_j}. With the per-signature GT table
    # (sig_gt_table) the pairing collapses to gtA[i][φ_j]^(−s_j·v_ij):
    # e(−sB, vA[φ]) = e(B, A[φ])^(−sv) by bilinearity. With per-base window
    # tables (sig_gt_pow_tables) the pow itself collapses to a gather + 63
    # GT muls, no squarings (gt_pow_fixed_multi).
    if gtA_pow is not None:
        ns_srv = v.shape[0]
        sv = B.fn_mul_plain(s, v)                          # (ns, V, l, 16)
        base_idx = (jnp.arange(ns_srv, dtype=jnp.int32)[:, None, None] * u
                    + digits[None].astype(jnp.int32))      # (ns, V, l)
        gt1 = _gt_pow_multi(gtA_pow, base_idx, B.fn_neg(sv))
    elif gtA is not None:
        gt_sel = gtA[:, digits]                            # (ns, V, l, 6,2,16)
        sv = B.fn_mul_plain(s, v)                          # (ns, V, l, 16)
        gt1 = B.gt_pow(gt_sel, B.fn_neg(sv))
    else:
        neg_s = B.fn_neg(s)
        nsB = B.fixed_base_mul(base_tbl, neg_s)            # (V, l, 3, 16)
        px, py, _ = B.g1_normalize(nsB)
        qx, qy, _ = B.g2_normalize(V_pts)
        sync(qx)
        gt1 = B.pair(px, py, qx, qy)                       # (ns, V, l, 6,2,16)
    sync(gt1)
    gt2 = gt_pow_gtb(t)                                    # (V, l, 6, 2, 16)
    a = B.gt_mul(gt1, gt2)

    return D, m_tot, V_pts, a


def _commit_kernel_sharded(digits, s, t, m, v, A_tab, ca_tbl, u: int, l: int,
                           gtA=None, gtA_pow=None, n_shards: int | None = None):
    """Mesh-sharded commitment stage: the value axis V is the `dp` axis
    (create_range_proof_lists_batched flattens n_dps*V onto it), so each DP
    shard builds its slice's a_ij GT-table exponentiations locally through
    the SAME per-shard `_commit_kernel` programs, and the commitments are
    gathered once per batch before the Fiat-Shamir hash.

    Bit-identical to one `_commit_kernel` call: proofs are per-value
    independent, the bucketed programs pad inactive lanes away, and the
    challenge hash runs over the gathered (concatenated) commitments —
    tests/test_proof_mesh.py asserts byte-equal payloads."""
    from ..parallel import proof_plane as plane

    if n_shards is None:
        n_shards = plane.n_shards()
    V = int(digits.shape[0])
    slices = plane.shard_slices(V, n_shards)
    if len(slices) <= 1:
        return _commit_kernel(digits, s, t, m, v, A_tab, ca_tbl, u, l,
                              gtA=gtA, gtA_pow=gtA_pow)

    def stage_commit(i, a, b):
        # only the per-shard slices are committed to shard i's device; the
        # shared tables (base/ca/A/gtA) stay uncommitted and follow the
        # committed operands onto each shard's device. The slices are
        # one-shot, so their buffers are donated to the upload; staging
        # overlaps the previous shard's compute (dispatch_shards).
        return plane.put_shard(
            (digits[a:b], s[a:b], t[a:b], m[a:b], v[:, a:b]), i,
            donate=True)

    def shard_commit(i, sd, ss, st, sm, sv):
        return _commit_kernel(sd, ss, st, sm, sv, A_tab, ca_tbl, u, l,
                              gtA=gtA, gtA_pow=gtA_pow)

    parts = plane.dispatch_shards(
        "CreateShard", shard_commit, [(a, b) for (a, b) in slices],
        prefetch=stage_commit)
    D = jnp.concatenate([p[0] for p in parts], axis=0)
    m_tot = jnp.concatenate([p[1] for p in parts], axis=0)
    V_pts = jnp.concatenate([p[2] for p in parts], axis=1)
    a_out = jnp.concatenate([p[3] for p in parts], axis=1)
    return D, m_tot, V_pts, a_out


def _response_kernel(digits, c, rs, s, t, m_tot, v):
    """Response stage: given the bound challenge c, compute
    Zphi_j = s_j − c·φ_j, Zr = Σm − c·r, Zv_ij = t_j − c·v_ij."""
    from ..crypto import batching as B

    phi = eg.int_to_scalar(digits.astype(jnp.int64))      # (V, l, 16)
    c_l = c[..., None, :]
    zphi = B.fn_sub(s, B.fn_mul_plain(c_l, phi))
    zr = B.fn_sub(m_tot, B.fn_mul_plain(c, rs))
    zv = B.fn_sub(t, B.fn_mul_plain(c_l, v))
    return zphi, zr, zv


def create_range_proofs(key, secrets, rs, cts, sigs: list[RangeSig],
                        u: int, l: int, ca_pub_table,
                        use_gt_table: bool = True,
                        shard: bool | None = None,
                        tile: int | None = None) -> RangeProofBatch:
    """Create proofs for V values at once.

    secrets: int64 (V,) plaintexts; rs: (V, 16) encryption blinding scalars;
    cts: (V, 2, 3, 16) their ciphertexts under the collective key;
    ca_pub_table: fixed-base table of the collective key P.
    (Reference CreatePredicateRangeProofForAllServ, range_proof.go:320-407.)

    use_gt_table: compute a_ij via the cached e(B, A[k]) table (one GT
    exponentiation per digit) instead of a pairing per digit — u*ns one-time
    pairings amortized over every proof against these signatures.

    shard: split the commitment stage over the proof-plane devices along
    the value (`dp`) axis; None = shard iff the plane is enabled
    (parallel/proof_plane.py — the default on a >= 2-device mesh).
    Transcripts are bit-identical either way.

    tile: cap every commit-stage dispatch at `tile` values — the
    bucket-tile path for grid-encoded surveys (encoding/tiles.py), where
    V reaches the reference's 1k..1M bucket axis and a single dispatch
    would materialize the whole (ns, V, l, 6, 2, 16) GT tensor at once.
    None = auto (tiles above tiles.TILE_THRESHOLD, the default at
    scale); 0 = never tile. The per-value randomness is drawn in the
    SAME four full-size calls either way and the Fiat-Shamir challenge
    is hashed per value from the gathered commitments, so the tiled
    transcripts are byte-identical to the monolithic path.
    """
    from ..encoding import tiles as _tiles

    V = int(np.asarray(secrets).shape[0])
    ns = len(sigs)
    digits = jnp.asarray(to_base(np.asarray(secrets), u, l), dtype=jnp.int32)  # (V, l)

    ks = jax.random.split(key, 4)
    s = eg.random_scalars(ks[0], (V, l))
    t = eg.random_scalars(ks[1], (V, l))
    m = eg.random_scalars(ks[2], (V, l))
    v = eg.random_scalars(ks[3], (ns, V, l))
    A_tab = jnp.asarray(np.stack([sg.A for sg in sigs]), dtype=jnp.uint32)   # (ns, u, 3, 2, 16)
    gtA = sig_gt_table(sigs) if use_gt_table else None
    # per-base window tables make the digit pow squaring-free on the Mosaic
    # path; the CPU/oracle path keeps the direct pow (no table build cost)
    from ..crypto import pallas_ops as po

    gtA_pow = (_sig_gt_pow_tables_dev(sigs)
               if use_gt_table and po.available() else None)

    # commit -> Fiat-Shamir (binds D, V_pts, a) -> respond. The canonical
    # commitment bytes are computed ONCE here and cached on the batch: they
    # are both the hash input and the wire format (to_bytes reuses them).
    from ..parallel import proof_plane as plane

    if shard is None:
        shard = plane.enabled()
    if tile is None:
        tile = _tiles.auto_tile(V)
    # shard count = max(plane policy, tile chunking): each per-tile
    # dispatch is bounded by the tile AND lands on a plane device
    n_shards = max(plane.n_shards() if shard else 1,
                   _tiles.proof_tile_shards(V, tile) if tile else 1)
    if n_shards > 1:
        D, m_tot, V_pts, a = _commit_kernel_sharded(
            digits, s, t, m, v, A_tab, ca_pub_table, u, l, gtA=gtA,
            gtA_pow=gtA_pow, n_shards=n_shards)
    else:
        D, m_tot, V_pts, a = _commit_kernel(
            digits, s, t, m, v, A_tab, ca_pub_table, u, l, gtA=gtA,
            gtA_pow=gtA_pow)
    wire = _range_wire_dict(cts, D, V_pts, a)
    c = jnp.asarray(challenge_from_wire(wire, sum_publics_bytes(sigs), u, l), dtype=jnp.uint32)
    zphi, zr, zv = _response_kernel(digits, c, jnp.asarray(rs, dtype=jnp.uint32), s, t,
                                    m_tot, v)
    return RangeProofBatch(commit=jnp.asarray(cts, dtype=jnp.uint32), challenge=c, zr=zr, d=D,
                           zphi=zphi, zv=zv, v_pts=V_pts, a=a, u=u, l=l,
                           wire=wire)


# ---------------------------------------------------------------------------
# Verification
# ---------------------------------------------------------------------------


def _verify_kernel(commit, c, zr, d, zphi, zv, v_pts, a, ys, ca_tbl,
                   u: int, l: int):
    """Batched verification. ys: (ns, 3, 16) server publics. Returns (V,)."""
    from ..crypto import batching as B
    from ..crypto import pallas_ops as po

    sync = jax.block_until_ready if po.available() else (lambda x: x)

    base_tbl = eg.BASE_TABLE.table
    upow_m = _upow_mont(u, l)

    # Dp = c·C2 + Zr·P + (Σ u^j Zphi_j)·B  ==  D   (range_proof.go:519-529)
    C2 = commit[..., 1, :, :]
    wz = _weighted_sum_mod_n(zphi, upow_m)
    Dp = B.g1_add(B.g1_scalar_mul(C2, c),
                  B.g1_add(B.fixed_base_mul(ca_tbl, zr),
                           B.fixed_base_mul(base_tbl, wz)))
    d_ok = B.g1_eq(Dp, d)                                  # (V,)
    sync(d_ok)

    # a'_ij = e(c·y_i − Zphi_j·B, V_ij) · gtB^{Zv_ij}  (:538-546)
    cy = B.g1_scalar_mul(ys[:, None, :, :], c[None, :, :])  # (ns, V, 3, 16)
    nzphiB = B.fixed_base_mul(base_tbl, B.fn_neg(zphi))    # (V, l, 3, 16)
    g1arg = B.g1_add(cy[:, :, None, :, :], nzphiB[None])   # (ns, V, l, 3, 16)
    px, py, _ = B.g1_normalize(g1arg)
    qx, qy, _ = B.g2_normalize(v_pts)
    sync(qx)
    gt1 = B.pair(px, py, qx, qy)
    sync(gt1)
    ap = B.gt_mul(gt1, gt_pow_gtb(zv))
    a_ok = jnp.all(F12.eq(ap, a), axis=(0, -1))            # (V,)

    return d_ok & a_ok


def verify_range_proofs(proof: RangeProofBatch, sigs_pub, ca_pub_table,
                        check_challenge: bool = True) -> np.ndarray:
    """Verify a proof batch against server publics (host affine int pairs).

    Returns bool (V,). (Reference RangeProofVerification :504-565; unlike
    it — which trusts the transmitted challenge — the recomputed
    Fiat-Shamir challenge over D ‖ V_pts ‖ a MUST match; this is the
    soundness-critical binding, see module docstring.)
    """
    ys = jnp.asarray(np.stack([C.from_ref(p) for p in sigs_pub]), dtype=jnp.uint32)
    ok = np.asarray(_verify_kernel(
        proof.commit, proof.challenge, proof.zr, proof.d, proof.zphi,
        proof.zv, proof.v_pts, proof.a, ys, ca_pub_table,
        proof.u, proof.l))
    if check_challenge:
        ok = ok & _challenge_ok(proof, sigs_pub)
    return ok


def _challenge_ok(proof: RangeProofBatch, sigs_pub) -> np.ndarray:
    """Recompute c = H(B ‖ C2 ‖ ΣY ‖ u ‖ l ‖ D ‖ V ‖ a) from the
    TRANSMITTED commitments and require equality with the transmitted
    challenge — a forger deriving D or a post-hoc changes c. Uses the
    wire-byte cache (pure host hashing; zero device work on the verifier)."""
    acc = None
    for p in sigs_pub:
        acc = refimpl.g1_add(acc, p)
    want = challenge_from_wire(proof.wire_bytes(), _g1_bytes_host(acc),
                               proof.u, proof.l)
    return np.all(np.asarray(proof.challenge) == want, axis=-1)


def verify_range_proofs_batch(proof: RangeProofBatch, sigs_pub, ca_pub_table,
                              check_challenge: bool = True,
                              rng: np.random.Generator | None = None) -> bool:
    """Single-verdict verification of a whole batch via a random linear
    combination in the exponent — ONE shared final exponentiation and ONE
    fixed-base gtB power for all ns*V*l digit proofs (vs one full reduced
    pairing + one 256-bit GT exponentiation each in the per-value path).

    Checks prod_ij [ e(r_ij*(c*y_i - Zphi_j*B), V_ij) * conj6(a_ij)^r_ij ]
           * gtB^(sum_ij r_ij*Zv_ij)  ==  1
    with verifier-secret 62-bit weights r_ij.

    Soundness (REQUIRES check_challenge=True — the service path always
    passes it): the Fiat-Shamir hash binds a (and D, V) BEFORE c is known,
    so the per-digit factor f_ij = e(c*y_i - Zphi_j*B, V_ij) *
    gtB^Zv_ij * conj6(a_ij) is fully determined by the transcript. For
    honest (cyclotomic, satisfying) a, conj6(a) = a^-1 and every f_ij = 1.
    A forged transcript has some f_ij != 1, and the check passes only if
    sum_ij r_ij * x_ij = 0 in the exponent lattice (x_ij = dlog of f_ij in
    the subgroup it generates): probability <= 2^-62 per independent r,
    unless f_ij has small order d (then 1/d). Two small-order routes are
    closed separately: (1) choosing a AFTER c (round-2 state) fails
    deterministically at the challenge recompute, since a is hashed into c
    (regression-tested by test_rlc_small_order_forgery_rejected); (2) a
    COMMIT-FIRST forger who sets a' = a_honest * eps BEFORE hashing, with
    eps a root of unity in GΦ12's cofactor subgroup (this curve's cofactor
    is divisible by 13 and 2749, so eps of order 13 exists), passes the
    challenge binding and the D equation and would survive the draw with
    probability 1/13 — that route is killed by rlc_prelude's order-n gate
    gt_order_ok (a^n == 1 via frob1(a) == a^(t-1)), which forces every
    wire a into the order-n subgroup where the only subgroup orders are 1
    and n (regression-tested by test_rlc_cofactor_forgery_rejected).

    The D-equation and Fiat-Shamir challenge are still checked per value
    (cheap G1 work). Returns one bool for the batch.
    """
    pre_ok, r_int, gtb_pow_s = rlc_prelude(
        proof, sigs_pub, ca_pub_table, rng=rng,
        check_challenge=check_challenge)
    if not pre_ok:
        return False  # D equation / challenge binding failed — deterministic

    total = rlc_total_single(proof, sigs_pub, r_int, gtb_pow_s)
    return bool(np.asarray(F12.eq(total, jnp.asarray(F12.one(), dtype=jnp.uint32))))


def rlc_total_single(proof: RangeProofBatch, sigs_pub, r_int, gtb_pow_s):
    """The RLC check's (6, 2, 16) GT total on ONE device — equals F12.one()
    iff the batch verifies under weights r_int. The pure single-device
    fallback of the proof plane: parallel/proof_mesh.rlc_total_shards
    computes the same total per-shard and MUST stay bit-identical to this
    (tests/test_proof_mesh.py asserts array equality under a shared
    weight draw)."""
    from ..crypto import batching as B
    from ..crypto import pallas_ops as po

    sync = jax.block_until_ready if po.available() else (lambda x: x)
    ys = jnp.asarray(np.stack([C.from_ref(p) for p in sigs_pub]), dtype=jnp.uint32)
    c, zphi = proof.challenge, proof.zphi
    base_tbl = eg.BASE_TABLE.table
    r = B.int_to_scalar(jnp.asarray(r_int, dtype=jnp.int64))               # (ns, V, l, 16)

    # r·(c·y_i − Zphi_j·B), then Miller only (final exp shared).
    # g1_scalar_mul64: the RLC weights are 62-bit, so the weighting ladder
    # runs 16 windows instead of 64
    cy = B.g1_scalar_mul(ys[:, None, :, :], c[None, :, :])
    nzphiB = B.fixed_base_mul(base_tbl, B.fn_neg(zphi))
    g1arg = B.g1_add(cy[:, :, None, :, :], nzphiB[None])  # (ns, V, l, 3, 16)
    g1arg_r = B.g1_scalar_mul64(g1arg, r)
    px, py, _ = B.g1_normalize(g1arg_r)
    qx, qy, _ = B.g2_normalize(proof.v_pts)
    sync(qx)
    m = B.miller(px, py, qx, qy)                          # (ns, V, l, 6,2,16)
    sync(m)
    ar = B.gt_pow64(F12.conj6(jnp.asarray(proof.a, dtype=jnp.uint32)), r)
    sync(ar)

    # final-exp ONLY the Miller product (the a^r factors are already in GT —
    # re-exponentiating them by h = (p^12-1)/n would scale their exponents
    # by h mod n != 1 and break the identity)
    fe = B.final_exp(B.gt_reduce_prod(
        m.reshape(-1, 6, 2, params.NUM_LIMBS))[None])
    Pa = B.gt_reduce_prod(ar.reshape(-1, 6, 2, params.NUM_LIMBS))

    # gtB^(Σ r·Zv) comes from the shared prelude (one fixed-base power)
    return B.gt_mul(B.gt_mul(fe, Pa[None]), gtb_pow_s[None])[0]


def rlc_prelude(proof: RangeProofBatch, sigs_pub, ca_pub_table,
                rng: np.random.Generator | None = None,
                check_challenge: bool = True, with_gtb_pow: bool = True):
    """The RLC verifiers' shared acceptance preamble — kept in ONE place so
    the single-device path (verify_range_proofs_batch) and the mesh-sharded
    path (parallel/proof_mesh.rlc_verify_sharded) cannot drift apart on the
    soundness-critical checks:

      * per-value D equation  D == c*C2 + Zr*P + (sum u^j Zphi_j)*B
      * binding Fiat-Shamir challenge recompute over D ‖ V ‖ a
      * GΦ12 membership of every wire-provided a (gt_membership_ok —
        required before the cyclotomic-squaring pow chains touch them)
      * order-n membership of every a (gt_order_ok, frob1(a) == a^(t-1)):
        GΦ12 alone leaves the cofactor subgroup open, and this curve's
        cofactor is divisible by 13 — a commit-first forger injecting a
        13th root of unity into a would otherwise survive the RLC draw
        with probability 1/13 (round-4 advisor finding)
      * verifier-secret 62-bit RLC weights r
      * [with_gtb_pow] gtB^(sum_ij r_ij*Zv_ij), the one fixed-base power

    Returns (pre_ok, r_int, gtb_pow_s) with gtb_pow_s None unless
    requested."""
    from ..crypto import batching as B

    base_tbl = eg.BASE_TABLE.table
    u, l = proof.u, proof.l
    ns, V = len(sigs_pub), proof.n_values
    upow_m = _upow_mont(u, l)

    C2 = jnp.asarray(proof.commit, dtype=jnp.uint32)[..., 1, :, :]
    wz = _weighted_sum_mod_n(proof.zphi, upow_m)
    Dp = B.g1_add(B.g1_scalar_mul(C2, proof.challenge),
                  B.g1_add(B.fixed_base_mul(ca_pub_table, proof.zr),
                           B.fixed_base_mul(base_tbl, wz)))
    ok = bool(np.all(np.asarray(B.g1_eq(Dp, proof.d))))
    if check_challenge:
        ok = ok and bool(np.all(_challenge_ok(proof, sigs_pub)))
    ok = ok and B.gt_membership_ok(proof.a) and B.gt_order_ok(proof.a)

    if rng is None:
        rng = np.random.default_rng(
            np.frombuffer(secrets.token_bytes(16), dtype=np.uint64))
    r_int = rng.integers(1, 1 << 62, size=(ns, V, l), dtype=np.int64)

    gtb_pow_s = None
    if with_gtb_pow:
        r = B.int_to_scalar(jnp.asarray(r_int, dtype=jnp.int64))
        rs_zv = B.fn_mul_plain(r, jnp.asarray(proof.zv, dtype=jnp.uint32)).reshape(
            -1, params.NUM_LIMBS)
        S = B.tree_reduce_add(rs_zv, B.fn_add, axis=0)
        gtb_pow_s = gt_pow_gtb(S[None])[0]
    return ok, r_int, gtb_pow_s




# ---------------------------------------------------------------------------
# Mixed-range proof lists (per-value (u, l) specs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RangeProofList:
    """Per-DP proof payload for an output vector with PER-VALUE range specs
    (reference creates/verifies each output with its own (u,l):
    lib/range/range_proof.go:320-407, lib/structs.go:446-533). Values sharing
    a spec are batched into one RangeProofBatch — the TPU grouping — with the
    output indices each batch covers. Indices whose spec is (0,0) carry no
    proof (reference: zero ranges mean 'unproved')."""

    n_values: int
    batches: list                      # [(int64 idx array, RangeProofBatch)]

    def to_bytes(self) -> bytes:
        head = np.asarray([self.n_values, len(self.batches)],
                          dtype="<i8").tobytes()
        parts = [head]
        for idx, pb in self.batches:
            blob = pb.to_bytes()
            idx = np.asarray(idx, dtype="<i8")
            parts.append(np.asarray([idx.size, len(blob)],
                                    dtype="<i8").tobytes())
            parts.append(idx.tobytes())
            parts.append(blob)
        return b"".join(parts)

    @classmethod
    def from_bytes(cls, buf: bytes) -> "RangeProofList":
        n_values, n_batches = np.frombuffer(buf[:16], dtype="<i8")
        off = 16
        batches = []
        for _ in range(int(n_batches)):
            n_idx, n_blob = np.frombuffer(buf[off:off + 16], dtype="<i8")
            off += 16
            idx = np.frombuffer(buf[off:off + 8 * int(n_idx)], dtype="<i8")
            off += 8 * int(n_idx)
            pb = RangeProofBatch.from_bytes(buf[off:off + int(n_blob)])
            off += int(n_blob)
            batches.append((idx.copy(), pb))
        return cls(n_values=int(n_values), batches=batches)


def group_ranges(ranges) -> dict:
    """{(u, l): [output indices]} for nonzero specs, insertion-ordered."""
    spec_to_idx: dict = {}
    for i, (u, l) in enumerate(ranges):
        if u == 0 and l == 0:
            continue
        spec_to_idx.setdefault((int(u), int(l)), []).append(i)
    return spec_to_idx


def create_range_proof_list(key, secrets, rs, cts, ranges,
                            sigs_by_u: dict, ca_pub_table,
                            tile: int | None = None) -> RangeProofList:
    """Create the per-DP mixed-range payload.

    ranges: [(u, l)] per output index; sigs_by_u: {u: [RangeSig per CN]}.
    tile: forwarded to create_range_proofs (None = auto bucket-tiling
    above the threshold — the grid-op scale path).
    """
    secrets = np.asarray(secrets)
    batches = []
    for (u, l), idx in group_ranges(ranges).items():
        key, sub = jax.random.split(key)
        ia = np.asarray(idx, dtype=np.int64)
        pb = create_range_proofs(
            sub, secrets[ia], jnp.asarray(rs, dtype=jnp.uint32)[ia], jnp.asarray(cts, dtype=jnp.uint32)[ia],
            sigs_by_u[u], u, l, ca_pub_table, tile=tile)
        batches.append((ia, pb))
    return RangeProofList(n_values=len(ranges), batches=batches)


def _slice_batch(pb: RangeProofBatch, sel: np.ndarray) -> RangeProofBatch:
    """Sub-batch along the value axis (proofs are per-value independent)."""
    wire = None
    if pb.wire is not None:
        ns = np.asarray(sel)
        wire = {"commit": pb.wire["commit"].reshape(
                    pb.n_values, 128)[ns],
                "d": pb.wire["d"][ns], "v": pb.wire["v"][:, ns],
                "a": pb.wire["a"][:, ns]}
    sel = jnp.asarray(sel)  # drynx: noqa[implicit-dtype]  (generic index array)
    return RangeProofBatch(
        commit=jnp.asarray(pb.commit, dtype=jnp.uint32)[sel], challenge=pb.challenge[sel],
        zr=pb.zr[sel], d=pb.d[sel], zphi=pb.zphi[sel],
        zv=pb.zv[:, sel], v_pts=pb.v_pts[:, sel], a=pb.a[:, sel],
        u=pb.u, l=pb.l, wire=wire)


def create_range_proof_lists_batched(key, secrets_2d, rs_2d, cts_2d, ranges,
                                     sigs_by_u: dict,
                                     ca_pub_table,
                                     tile: int | None = None) -> list:
    """All DPs' payloads in ONE device-batched creation (the single-chip
    harness path: n_dps DPs share the chip, so their per-value-independent
    proofs vectorize into one kernel chain instead of n_dps serialized
    ones — the reference's DPs parallelize the same work across machines,
    data_collection_protocol.go:279-347).

    secrets_2d: (n_dps, V); rs_2d: (n_dps, V, 16); cts_2d: (n_dps, V, 2, 3,
    16); ranges: per-output (u, l) specs (shared by every DP). Returns
    [RangeProofList] per DP, each byte-compatible with per-DP creation
    (same per-value transcripts — the Fiat-Shamir challenge hash is
    per-value, so batching does not change any proof)."""
    secrets_2d = np.asarray(secrets_2d)
    n_dps, V = secrets_2d.shape
    flat_ranges = list(ranges) * n_dps
    big = create_range_proof_list(
        key, secrets_2d.reshape(-1), jnp.asarray(rs_2d, dtype=jnp.uint32).reshape(-1, 16),
        jnp.asarray(cts_2d, dtype=jnp.uint32).reshape(-1, 2, 3, 16), flat_ranges, sigs_by_u,
        ca_pub_table, tile=tile)
    out = []
    for d in range(n_dps):
        batches = []
        for ia, pb in big.batches:
            ia = np.asarray(ia)
            mine = (ia // V) == d
            if not np.any(mine):
                continue
            local_idx = (ia[mine] % V).astype(np.int64)
            batches.append((local_idx, _slice_batch(pb, np.nonzero(mine)[0])))
        out.append(RangeProofList(n_values=V, batches=batches))
    return out


def _batch_shapes_ok(pb: RangeProofBatch, ns_expected: int) -> bool:
    """Tensor-shape consistency for a WIRE-DECODED batch: from_bytes trusts
    the payload's own (u, l, V, ns) header, so a malicious DP can ship a
    structurally-'valid' object whose ns disagrees with the published
    signature roster or whose tensors disagree with each other — the joint
    concat/broadcast would then raise and (before this guard) poison honest
    neighbours' verdicts via the flush-level catch-all."""
    NLb = params.NUM_LIMBS
    try:
        ns, l, V = pb.n_servers, int(pb.l), pb.n_values
        return (ns == ns_expected and l >= 1 and V >= 1
                and tuple(pb.commit.shape) == (V, 2, 3, NLb)
                and tuple(pb.challenge.shape) == (V, NLb)
                and tuple(pb.zr.shape) == (V, NLb)
                and tuple(pb.d.shape) == (V, 3, NLb)
                and tuple(pb.zphi.shape) == (V, l, NLb)
                and tuple(pb.zv.shape) == (ns, V, l, NLb)
                and tuple(pb.v_pts.shape) == (ns, V, l, 3, 2, NLb)
                and tuple(pb.a.shape) == (ns, V, l, 6, 2, NLb))
    except Exception:
        return False


def _list_structure_ok(lst: RangeProofList, ranges,
                       sigs_pub_by_u: dict) -> bool:
    """Coverage check: every output index with a nonzero (u, l) spec must be
    covered by exactly one batch carrying that exact spec (a prover cannot
    substitute a looser range), every batch's base must have published
    signatures, and every batch's ns/tensor shapes must be self-consistent
    (see _batch_shapes_ok)."""
    want = group_ranges(ranges)
    covered = {}
    for ia, pb in lst.batches:
        sigs = sigs_pub_by_u.get(pb.u)
        if sigs is None:
            return False
        if not _batch_shapes_ok(pb, len(sigs)):
            return False
        if len(np.asarray(ia)) != pb.n_values:
            return False
        for i in ia:
            if int(i) in covered:
                return False
            covered[int(i)] = (pb.u, pb.l)
    for (u, l), idx in want.items():
        for i in idx:
            if covered.get(i) != (u, l):
                return False
    return set(covered) == {i for idx in want.values() for i in idx}


def _safe_batch_verify(pb: RangeProofBatch, sigs_pub, ca_pub_table) -> bool:
    """The joint-range verification routing point, with exception
    containment.

    Routing: whenever the proof plane is enabled (>= 2 visible devices,
    parallel/proof_plane.py), the DEFAULT path is the mesh-sharded verifier
    — VN-role devices each verify a proof shard, combined by one GT
    product. Its accept/reject decision is bit-identical to the
    single-device verifier (same rlc_prelude, same per-element programs,
    exact GT arithmetic), so the soundness semantics cannot differ. A
    sharded-path FAILURE (an exception, not a False verdict) falls back to
    the single-device verifier: a plane bug must not reject honest
    payloads.

    Containment: a payload that still manages to crash the kernels
    (despite _batch_shapes_ok) is a FAILED verification for ITSELF — the
    exception must never propagate to the flush-level catch-all, which
    would mark every sampled payload BM_FALSE and poison honest DPs'
    audit entries."""
    try:
        from ..parallel import proof_plane as plane

        if plane.enabled():
            from ..parallel import proof_mesh as pm

            try:
                return pm.rlc_verify_sharded(pb, sigs_pub, ca_pub_table)
            except Exception:
                import traceback

                from ..utils import log

                log.warn("sharded verify raised — falling back to the "
                         "single-device verifier: "
                         + traceback.format_exc(limit=8))
        return verify_range_proofs_batch(pb, sigs_pub, ca_pub_table)
    except Exception:
        import traceback

        from ..utils import log

        log.warn("range batch verify raised (payload rejected): "
                 + traceback.format_exc(limit=8))
        return False


def verify_range_proof_list(lst: RangeProofList, ranges,
                            sigs_pub_by_u: dict, ca_pub_table) -> bool:
    """Verify a mixed-range payload against the QUERY's specs (structure +
    every batch's RLC check)."""
    if not _list_structure_ok(lst, ranges, sigs_pub_by_u):
        return False
    for ia, pb in lst.batches:
        if not _safe_batch_verify(pb, sigs_pub_by_u[pb.u], ca_pub_table):
            return False
    return True


def _concat_batches(pbs: list) -> RangeProofBatch:
    """Concatenate same-spec batches along the value axis."""
    u, l = pbs[0].u, pbs[0].l
    assert all(pb.u == u and pb.l == l for pb in pbs)
    cat = lambda xs, ax: jnp.concatenate([jnp.asarray(x, dtype=jnp.uint32) for x in xs], ax)
    wire = None
    if all(pb.wire is not None for pb in pbs):
        wire = {"commit": np.concatenate(
                    [pb.wire["commit"].reshape(pb.n_values, 128)
                     for pb in pbs], 0),
                "d": np.concatenate([pb.wire["d"] for pb in pbs], 0),
                "v": np.concatenate([pb.wire["v"] for pb in pbs], 1),
                "a": np.concatenate([pb.wire["a"] for pb in pbs], 1)}
    return RangeProofBatch(
        commit=cat([pb.commit for pb in pbs], 0),
        challenge=cat([pb.challenge for pb in pbs], 0),
        zr=cat([pb.zr for pb in pbs], 0),
        d=cat([pb.d for pb in pbs], 0),
        zphi=cat([pb.zphi for pb in pbs], 0),
        zv=cat([pb.zv for pb in pbs], 1),
        v_pts=cat([pb.v_pts for pb in pbs], 1),
        a=cat([pb.a for pb in pbs], 1), u=u, l=l, wire=wire)


def verify_range_proof_payloads_joint(datas: list, ranges,
                                      sigs_pub_by_u: dict,
                                      ca_pub_table) -> list[bool]:
    """Joint verification from RAW payload bytes: each payload deserializes
    in its own guard so one malformed (malicious) payload fails only
    itself — never its honest neighbours."""
    lists: list = []
    idx: list = []
    out = [False] * len(datas)
    for i, d in enumerate(datas):
        try:
            lists.append(RangeProofList.from_bytes(d))
            idx.append(i)
        except Exception:
            from ..utils import log

            log.warn(f"range payload {i}: malformed bytes, rejected")
    if lists:
        for i, ok in zip(idx, verify_range_proof_lists_joint(
                lists, ranges, sigs_pub_by_u, ca_pub_table)):
            out[i] = ok
    return out


def verify_range_proof_lists_joint(lists: list, ranges, sigs_pub_by_u: dict,
                                   ca_pub_table) -> list[bool]:
    """Joint verification of MANY payloads (one per DP): structural checks
    per payload, then ONE RLC batch verification per (u, l) spec over the
    concatenation of every structurally-valid payload's values — a VN
    verifying 10 DPs' proofs pays one shared final exponentiation instead
    of 10 (sound: the RLC weights are drawn across the whole concatenation,
    and each per-value transcript is independent). On a joint failure,
    falls back to per-payload verification so honest payloads are not
    penalized for a neighbour's forgery. Returns one bool per payload."""
    ok_struct = [_list_structure_ok(lst, ranges, sigs_pub_by_u)
                 for lst in lists]
    idx_valid = [i for i, ok in enumerate(ok_struct) if ok]
    if not idx_valid:
        return ok_struct

    by_spec: dict = {}
    for i in idx_valid:
        for _ia, pb in lists[i].batches:
            by_spec.setdefault((pb.u, pb.l), []).append(pb)
    joint_ok = all(
        _safe_batch_verify(_concat_batches(pbs), sigs_pub_by_u[u],
                           ca_pub_table)
        for (u, _l), pbs in by_spec.items())
    if joint_ok:
        return ok_struct
    return [ok_struct[i] and verify_range_proof_list(
        lists[i], ranges, sigs_pub_by_u, ca_pub_table)
        for i in range(len(lists))]


def verify_cross_survey_payloads_joint(payloads_by_sid: dict,
                                       expected_by_sid: dict,
                                       sigs_pub_by_u: dict,
                                       ca_pub_table) -> dict:
    """Joint verification across SURVEYS: the lists_joint algebra one level
    up. Every queued survey's structurally-valid batches at the same
    (u, l) spec concatenate along the value axis into ONE RLC batch check —
    one shared final exponentiation for the whole queue, not one per survey
    (sound for the same reason as within-survey batching: the RLC weights
    are drawn across the whole concatenation and per-value transcripts are
    independent; bit-identity of the GT algebra is asserted by
    tests/test_server.py).

    Isolation ladder on a joint failure: fall back to PER-SURVEY joint
    verification (verify_range_proof_lists_joint), which itself falls back
    to per-payload — so one tampered survey in the batch costs one retry
    level, never its neighbours' verdicts. A survey with expected=None
    (the CN no longer knows it) verifies all-False.

    Returns {survey_id: [bool per payload, in input order]}."""
    lists_by_sid: dict = {}
    out = {sid: [False] * len(datas)
           for sid, datas in payloads_by_sid.items()}
    for sid, datas in payloads_by_sid.items():
        if expected_by_sid.get(sid) is None:
            continue
        entries = []
        for i, d in enumerate(datas):
            try:
                entries.append((i, RangeProofList.from_bytes(d)))
            except Exception:
                from ..utils import log

                log.warn(f"survey {sid} range payload {i}: malformed "
                         f"bytes, rejected")
        lists_by_sid[sid] = entries

    ok_struct: dict = {}
    by_spec: dict = {}
    for sid, entries in lists_by_sid.items():
        ranges = expected_by_sid[sid]
        ok_struct[sid] = {
            i: _list_structure_ok(lst, ranges, sigs_pub_by_u)
            for i, lst in entries}
        for i, lst in entries:
            if not ok_struct[sid][i]:
                continue
            for _ia, pb in lst.batches:
                by_spec.setdefault((pb.u, pb.l), []).append(pb)

    joint_ok = all(
        _safe_batch_verify(_concat_batches(pbs), sigs_pub_by_u[u],
                           ca_pub_table)
        for (u, _l), pbs in by_spec.items())
    for sid, entries in lists_by_sid.items():
        if joint_ok:
            for i, _lst in entries:
                out[sid][i] = ok_struct[sid][i]
        else:
            ranges = expected_by_sid[sid]
            verdicts = verify_range_proof_lists_joint(
                [lst for _i, lst in entries], ranges, sigs_pub_by_u,
                ca_pub_table)
            for (i, _lst), ok in zip(entries, verdicts):
                out[sid][i] = ok
    return out


__all__ = ["RangeSig", "init_range_sig", "sig_gt_table", "to_base",
           "RangeProofBatch",
           "RangeProofList", "group_ranges", "create_range_proofs",
           "create_range_proof_list", "create_range_proof_lists_batched",
           "verify_range_proofs", "verify_range_proofs_batch",
           "verify_range_proof_list", "verify_range_proof_lists_joint",
           "verify_range_proof_payloads_joint",
           "verify_cross_survey_payloads_joint", "rlc_prelude",
           "rlc_total_single", "proof_challenge", "gt_base",
           "gt_base_table", "gt_pow_gtb", "sum_publics_bytes"]

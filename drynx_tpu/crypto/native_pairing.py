"""ctypes binding to the native C++ pairing backend (native/pairing.cpp).

Fills the role the reference's native Go crypto plays on CPU (kyber bn256,
lib/suite.go:10-20): the same optimal-ate math as crypto/refimpl.py — the
C++ mirrors refimpl operation for operation, with all constants generated
from the Python parameters (scripts/gen_native_constants.py) — at native
Montgomery-limb speed. crypto/host_oracle.py dispatches here when the
library is available; the pure-Python oracle remains the fallback and the
authority every backend (this one included) is parity-tested against
(tests/test_native_pairing.py asserts BIT-IDENTICAL outputs, Miller values
included).

Build: on demand with g++ (same pattern as service/store.py / proofdb).
Kill-switch: DRYNX_NATIVE_PAIR=0 disables loading entirely.

Layouts match crypto/batching.py: Fp = (…, 16) uint32 Montgomery limbs
(16 bits per word), G2/Fp2 coords (…, 2, 16), GT (…, 6, 2, 16); exponents
are (…, 16) PLAIN limbs. Infinity points are all-zero coordinates.
"""
from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from ..resilience.policy import named_lock

ENABLED = os.environ.get("DRYNX_NATIVE_PAIR", "1") == "1"

_ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
_SRC = os.path.join(_ROOT, "native", "pairing.cpp")
_HDR = os.path.join(_ROOT, "native", "pairing_constants.h")
_LIB_DIR = os.path.join(_ROOT, "native", "build")
_LIB_PATH = os.path.join(_LIB_DIR, "libdxpairing.so")
_BUILD_LOCK = named_lock("pairing_build_lock")
_LIB = None
_LIB_FAILED = False

_U32P = ctypes.POINTER(ctypes.c_uint32)
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _load():
    global _LIB, _LIB_FAILED
    if _LIB is not None or _LIB_FAILED or not ENABLED:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        try:
            from ..utils.native_build import build_native_lib

            build_native_lib([_SRC, _HDR], _LIB_PATH)
            lib = ctypes.CDLL(_LIB_PATH)
            for name, args in [
                ("dx_miller_batch", [_U32P] * 5 + [ctypes.c_uint64]),
                ("dx_pair_batch", [_U32P] * 5 + [ctypes.c_uint64]),
                ("dx_final_exp_batch", [_U32P, _U32P, ctypes.c_uint64]),
                ("dx_gt_pow_batch", [_U32P] * 3 + [ctypes.c_uint64]),
                ("dx_gt_cyc_pow_batch", [_U32P] * 3 + [ctypes.c_uint64]),
                ("dx_gt_mul_batch", [_U32P] * 3 + [ctypes.c_uint64]),
                ("dx_gt_frob_batch",
                 [_U32P, ctypes.c_int32, _U32P, ctypes.c_uint64]),
                ("dx_gt_order_check_batch",
                 [_U32P, _U32P, _U8P, ctypes.c_uint64]),
                ("dx_g1_scalar_mul_batch",
                 [_U32P, _U32P, ctypes.c_int32, _U32P, ctypes.c_uint64]),
                ("dx_g1_add_batch", [_U32P] * 3 + [ctypes.c_uint64]),
                ("dx_g1_neg_batch", [_U32P] * 2 + [ctypes.c_uint64]),
                ("dx_g1_eq_batch", [_U32P, _U32P, _U8P, ctypes.c_uint64]),
                ("dx_g1_normalize_batch",
                 [_U32P, _U32P, _U32P, _U8P, ctypes.c_uint64]),
                ("dx_g2_scalar_mul_batch",
                 [_U32P, _U32P, ctypes.c_int32, _U32P, ctypes.c_uint64]),
                ("dx_g2_normalize_batch",
                 [_U32P, _U32P, _U32P, _U8P, ctypes.c_uint64]),
            ]:
                fn = getattr(lib, name)
                fn.restype = None
                fn.argtypes = args
            _LIB = lib
        except Exception as e:  # no toolchain / build error: Python oracle
            from ..utils.native_build import warn_unavailable

            warn_unavailable(
                "native pairing backend", e,
                "the pure-Python oracle (30-80x slower); "
                "tests/test_native_pairing.py will SKIP")
            _LIB_FAILED = True
    return _LIB


def available() -> bool:
    return _load() is not None


def _c32(a: np.ndarray):
    return a.ctypes.data_as(_U32P)


def _prep(a, shape_tail) -> np.ndarray:
    a = np.ascontiguousarray(np.asarray(a), dtype=np.uint32)
    if a.shape[-len(shape_tail):] != shape_tail:
        raise ValueError(f"bad tail shape {a.shape} (want *{shape_tail})")
    return a.reshape((-1,) + shape_tail)


def miller_batch(px, py, qx, qy) -> np.ndarray:
    lib = _load()
    px, py = _prep(px, (16,)), _prep(py, (16,))
    qx, qy = _prep(qx, (2, 16)), _prep(qy, (2, 16))
    n = px.shape[0]
    if not (py.shape[0] == n and qx.shape[0] == n and qy.shape[0] == n):
        raise ValueError((px.shape, py.shape, qx.shape, qy.shape))
    out = np.empty((n, 6, 2, 16), dtype=np.uint32)
    lib.dx_miller_batch(_c32(px), _c32(py), _c32(qx), _c32(qy), _c32(out), n)
    return out


def pair_batch(px, py, qx, qy) -> np.ndarray:
    lib = _load()
    px, py = _prep(px, (16,)), _prep(py, (16,))
    qx, qy = _prep(qx, (2, 16)), _prep(qy, (2, 16))
    n = px.shape[0]
    if not (py.shape[0] == n and qx.shape[0] == n and qy.shape[0] == n):
        raise ValueError((px.shape, py.shape, qx.shape, qy.shape))
    out = np.empty((n, 6, 2, 16), dtype=np.uint32)
    lib.dx_pair_batch(_c32(px), _c32(py), _c32(qx), _c32(qy), _c32(out), n)
    return out


def final_exp_batch(f) -> np.ndarray:
    lib = _load()
    f = _prep(f, (6, 2, 16))
    out = np.empty_like(f)
    lib.dx_final_exp_batch(_c32(f), _c32(out), f.shape[0])
    return out


def gt_pow_batch(f, k) -> np.ndarray:
    lib = _load()
    f, k = _prep(f, (6, 2, 16)), _prep(k, (16,))
    if f.shape[0] != k.shape[0]:
        raise ValueError((f.shape, k.shape))
    out = np.empty_like(f)
    lib.dx_gt_pow_batch(_c32(f), _c32(k), _c32(out), f.shape[0])
    return out


def gt_cyc_pow_batch(f, k) -> np.ndarray:
    """Cyclotomic-squaring pow — f MUST be in GΦ12 (callers gate)."""
    lib = _load()
    f, k = _prep(f, (6, 2, 16)), _prep(k, (16,))
    if f.shape[0] != k.shape[0]:
        raise ValueError((f.shape, k.shape))
    out = np.empty_like(f)
    lib.dx_gt_cyc_pow_batch(_c32(f), _c32(k), _c32(out), f.shape[0])
    return out


def gt_mul_batch(a, b) -> np.ndarray:
    lib = _load()
    a, b = _prep(a, (6, 2, 16)), _prep(b, (6, 2, 16))
    if a.shape[0] != b.shape[0]:
        raise ValueError((a.shape, b.shape))
    out = np.empty_like(a)
    lib.dx_gt_mul_batch(_c32(a), _c32(b), _c32(out), a.shape[0])
    return out


def gt_frob_batch(f, e: int) -> np.ndarray:
    lib = _load()
    f = _prep(f, (6, 2, 16))
    out = np.empty_like(f)
    lib.dx_gt_frob_batch(_c32(f), ctypes.c_int32(e), _c32(out), f.shape[0])
    return out


def g1_scalar_mul_batch(p, k, nbits: int = 256) -> np.ndarray:
    """k*P batched: p (…, 3, 16) Jacobian Montgomery, k (…, 16) plain
    limbs (low `nbits` used); output canonical (Z=1 / Z=0-infinity)."""
    lib = _load()
    p, k = _prep(p, (3, 16)), _prep(k, (16,))
    if p.shape[0] != k.shape[0]:
        raise ValueError((p.shape, k.shape))
    out = np.empty_like(p)
    lib.dx_g1_scalar_mul_batch(_c32(p), _c32(k), ctypes.c_int32(nbits),
                               _c32(out), p.shape[0])
    return out


def g1_add_batch(a, b) -> np.ndarray:
    lib = _load()
    a, b = _prep(a, (3, 16)), _prep(b, (3, 16))
    if a.shape[0] != b.shape[0]:
        raise ValueError((a.shape, b.shape))
    out = np.empty_like(a)
    lib.dx_g1_add_batch(_c32(a), _c32(b), _c32(out), a.shape[0])
    return out


def g1_neg_batch(a) -> np.ndarray:
    lib = _load()
    a = _prep(a, (3, 16))
    out = np.empty_like(a)
    lib.dx_g1_neg_batch(_c32(a), _c32(out), a.shape[0])
    return out


def g1_eq_batch(a, b) -> np.ndarray:
    lib = _load()
    a, b = _prep(a, (3, 16)), _prep(b, (3, 16))
    if a.shape[0] != b.shape[0]:
        raise ValueError((a.shape, b.shape))
    ok = np.empty((a.shape[0],), dtype=np.uint8)
    lib.dx_g1_eq_batch(_c32(a), _c32(b), ok.ctypes.data_as(_U8P), a.shape[0])
    return ok.astype(bool)


def g1_normalize_batch(p):
    """(…, 3, 16) -> (x (…, 16), y (…, 16), inf (…,) bool); infinity rows
    get zero coords (the canonical-bytes encoder masks them anyway)."""
    lib = _load()
    p = _prep(p, (3, 16))
    n = p.shape[0]
    x = np.empty((n, 16), dtype=np.uint32)
    y = np.empty((n, 16), dtype=np.uint32)
    inf = np.empty((n,), dtype=np.uint8)
    lib.dx_g1_normalize_batch(_c32(p), _c32(x), _c32(y),
                              inf.ctypes.data_as(_U8P), n)
    return x, y, inf.astype(bool)


def g2_scalar_mul_batch(p, k, nbits: int = 256) -> np.ndarray:
    """k*Q batched: p (…, 3, 2, 16) Jacobian Montgomery twist points,
    k (…, 16) plain limbs; output canonical (Z=1 / Z=0-infinity)."""
    lib = _load()
    p, k = _prep(p, (3, 2, 16)), _prep(k, (16,))
    if p.shape[0] != k.shape[0]:
        raise ValueError((p.shape, k.shape))
    out = np.empty_like(p)
    lib.dx_g2_scalar_mul_batch(_c32(p), _c32(k), ctypes.c_int32(nbits),
                               _c32(out), p.shape[0])
    return out


def g2_normalize_batch(p):
    lib = _load()
    p = _prep(p, (3, 2, 16))
    n = p.shape[0]
    x = np.empty((n, 2, 16), dtype=np.uint32)
    y = np.empty((n, 2, 16), dtype=np.uint32)
    inf = np.empty((n,), dtype=np.uint8)
    lib.dx_g2_normalize_batch(_c32(p), _c32(x), _c32(y),
                              inf.ctypes.data_as(_U8P), n)
    return x, y, inf.astype(bool)


def gt_order_check_batch(f) -> np.ndarray:
    """Order-n gate verdicts: ok[i] = frob1(f_i) == f_i^(p-n)  (⇔ f^n = 1
    within GΦ12 — callers must have gated membership first)."""
    from . import params

    lib = _load()
    f = _prep(f, (6, 2, 16))
    t1 = np.asarray(params.to_limbs(params.P - params.N), dtype=np.uint32)
    ok = np.empty((f.shape[0],), dtype=np.uint8)
    lib.dx_gt_order_check_batch(_c32(f), _c32(t1), ok.ctypes.data_as(_U8P),
                                f.shape[0])
    return ok.astype(bool)


__all__ = ["ENABLED", "available", "miller_batch", "pair_batch",
           "final_exp_batch", "gt_pow_batch", "gt_cyc_pow_batch",
           "gt_mul_batch", "gt_frob_batch", "gt_order_check_batch",
           "g1_scalar_mul_batch", "g1_add_batch", "g1_neg_batch",
           "g1_eq_batch", "g1_normalize_batch",
           "g2_scalar_mul_batch", "g2_normalize_batch"]

"""Proof store: ctypes binding to the native C++ append-only KV log.

Replaces the reference's bbolt embedded store (OpenDB at
services/service_skipchain.go:489, puts at
protocols/proof_collection_protocol.go:318-359). The native library is
compiled on demand with g++ (no pip deps); if the toolchain is unavailable a
pure-Python fallback with the same API keeps tests running.
"""
from __future__ import annotations

import ctypes
import dataclasses
import json
import os
import threading

from ..resilience.policy import named_lock

# DRYNX_DET_TRACE: hash every ProofDB write into the runtime
# determinism recorder (analysis/dettrace.py) — the dynamic half of
# the nondeterminism-taint cross-check. Covers pane:/ckpt: blobs,
# skipchain blocks and checkpoint persistence, all of which land here.
_DET_TRACE = os.environ.get("DRYNX_DET_TRACE", "0") == "1"

# DRYNX_PROTO_TRACE: report SurveyCheckpoint lifecycle events
# (ctor/load/enter/save) to the runtime protocol recorder
# (analysis/prototrace.py) — the dynamic half of the seal-commit-once
# typestate rule's checkpoint clause.
_PROTO_TRACE = os.environ.get("DRYNX_PROTO_TRACE", "0") == "1"

_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "native",
                    "proofdb.cpp")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native",
                        "build")
_LIB_PATH = os.path.join(_LIB_DIR, "libproofdb.so")
_BUILD_LOCK = named_lock("proofdb_build_lock")
_LIB = None
_LIB_FAILED = False


def _load_lib():
    global _LIB, _LIB_FAILED
    if _LIB is not None or _LIB_FAILED:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        try:
            from ..utils.native_build import build_native_lib

            build_native_lib([_SRC], _LIB_PATH)
            lib = ctypes.CDLL(_LIB_PATH)
            lib.pdb_open.restype = ctypes.c_void_p
            lib.pdb_open.argtypes = [ctypes.c_char_p]
            lib.pdb_put.restype = ctypes.c_int
            lib.pdb_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint32, ctypes.c_char_p,
                                    ctypes.c_uint32]
            lib.pdb_get.restype = ctypes.c_int64
            lib.pdb_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint32, ctypes.c_char_p,
                                    ctypes.c_uint64]
            lib.pdb_count.restype = ctypes.c_int64
            lib.pdb_count.argtypes = [ctypes.c_void_p]
            lib.pdb_key_at.restype = ctypes.c_int64
            lib.pdb_key_at.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.c_char_p, ctypes.c_uint64]
            lib.pdb_sync.restype = ctypes.c_int
            lib.pdb_sync.argtypes = [ctypes.c_void_p]
            lib.pdb_close.restype = None
            lib.pdb_close.argtypes = [ctypes.c_void_p]
            _LIB = lib
        except Exception as e:  # no toolchain / build error
            from ..utils.native_build import warn_unavailable

            warn_unavailable("native proof store", e,
                             "the pure-Python ProofDB")
            _LIB_FAILED = True
    return _LIB


class ProofDB:
    """Keyed byte store, last-write-wins, persistent across reopen."""

    def __init__(self, path: str):
        self.path = path
        self._lock = named_lock("proofdb_lock")
        lib = _load_lib()
        if lib is not None:
            self._h = lib.pdb_open(path.encode())
            self._lib = lib
            if not self._h:
                raise OSError(f"proofdb: cannot open {path}")
        else:  # pure-Python fallback
            self._h = None
            self._lib = None
            self._mem: dict[bytes, bytes] = {}
            self._order: list[bytes] = []
            if os.path.exists(path):
                with open(path, "rb") as f:
                    buf = f.read()
                off = 0
                while off + 8 <= len(buf):
                    klen = int.from_bytes(buf[off:off + 4], "little")
                    vlen = int.from_bytes(buf[off + 4:off + 8], "little")
                    k = buf[off + 8:off + 8 + klen]
                    v = buf[off + 8 + klen:off + 8 + klen + vlen]
                    if len(v) < vlen:
                        break
                    if k not in self._mem:
                        self._order.append(k)
                    self._mem[k] = v
                    off += 8 + klen + vlen

    @property
    def native(self) -> bool:
        return self._lib is not None

    def _handle(self):
        """Native handle, reopened on demand: close() marks the DB closed,
        and later proof traffic transparently reopens the append-only log
        instead of crashing into a dangling handle (a remote close_db can
        arrive while the node keeps serving RPCs)."""
        if self._lib is not None and not self._h:
            self._h = self._lib.pdb_open(self.path.encode())
            if not self._h:
                raise OSError(f"proofdb: cannot reopen {self.path}")
        return self._h

    def put(self, key: str | bytes, value: bytes) -> None:
        k = key.encode() if isinstance(key, str) else key
        if _DET_TRACE:
            from ..analysis import dettrace
            dettrace.record("proofdb", k.decode("utf-8", "replace"),
                            value)
        with self._lock:
            if self._lib is not None:
                rc = self._lib.pdb_put(self._handle(), k, len(k), value,
                                       len(value))
                if rc != 0:
                    raise OSError("proofdb put failed")
            else:
                with open(self.path, "ab") as f:
                    f.write(len(k).to_bytes(4, "little")
                            + len(value).to_bytes(4, "little") + k + value)
                if k not in self._mem:
                    self._order.append(k)
                self._mem[k] = value

    def get(self, key: str | bytes) -> bytes | None:
        k = key.encode() if isinstance(key, str) else key
        with self._lock:
            if self._lib is not None:
                h = self._handle()
                n = self._lib.pdb_get(h, k, len(k), None, 0)
                if n < 0:
                    return None
                buf = ctypes.create_string_buffer(int(n))
                self._lib.pdb_get(h, k, len(k), buf, n)
                return buf.raw[:n]
            return self._mem.get(k)

    def keys(self) -> list[bytes]:
        with self._lock:
            if self._lib is not None:
                h = self._handle()
                out = []
                count = self._lib.pdb_count(h)
                for i in range(count):
                    n = self._lib.pdb_key_at(h, i, None, 0)
                    buf = ctypes.create_string_buffer(int(n))
                    self._lib.pdb_key_at(h, i, buf, n)
                    out.append(buf.raw[:n])
                return out
            return list(self._order)

    def sync(self) -> None:
        with self._lock:
            if self._lib is not None and self._h:
                self._lib.pdb_sync(self._h)

    def close(self) -> None:
        with self._lock:
            if self._lib is not None and self._h:
                self._lib.pdb_close(self._h)
                self._h = None


_CKPT_PREFIX = b"ckpt:"

# Streaming-survey pane cache (PR 18): sealed panes' range-proof blobs
# persist under the same append-only log as proofs and checkpoints, in a
# key prefix neither of those paths uses. A pane is immutable, so its
# cached blob is reused byte-identically by every window slide containing
# it — the store is the reuse, not just durability.
_PANE_PREFIX = b"pane:"


def pane_key(stream_id: str, pane_id: int, dp_name: str) -> bytes:
    """ProofDB key for one (stream, pane, DP) range-proof blob."""
    return _PANE_PREFIX + f"{stream_id}/{int(pane_id)}/{dp_name}".encode()


@dataclasses.dataclass
class SurveyCheckpoint:
    """Durable per-survey phase checkpoint (ROADMAP item 6, PR 17).

    One record per survey, overwritten (last-write-wins) at every phase
    entry: which phase the state machine is in, which DPs have
    contributed, and how many times each phase was entered. A mid-phase
    transport failure leaves the record at the failed phase; the resume
    lane re-enters with ``resumes`` bumped, and the phase counters are
    how the soak harness asserts "resumed from checkpoint, not
    restarted" (a restart would reset them). Persisted through
    :class:`ProofDB` so a root process restart resumes too —
    checkpoints ride the same append-only log as proofs, under the
    ``ckpt:`` key prefix the proof paths never use.
    """

    survey_id: str
    phase: str = "admitted"
    responders: list = dataclasses.field(default_factory=list)
    absent: list = dataclasses.field(default_factory=list)
    resumes: int = 0
    done: bool = False
    phase_entries: dict = dataclasses.field(default_factory=dict)
    progress: dict = dataclasses.field(default_factory=dict)

    def _proto_event(self, event: str) -> None:
        """Report a lifecycle event to the runtime protocol recorder.
        The token is minted lazily at the first event so the
        ``from_bytes`` constructor used by :meth:`load` doesn't record
        a spurious ``ctor`` before the ``load`` event."""
        from ..analysis import prototrace
        inst = getattr(self, "_proto_inst", None)
        if inst is None:
            inst = prototrace.new_instance("ckpt")
            self._proto_inst = inst
            if event != "load":
                prototrace.record(inst, "ctor")
        prototrace.record(inst, event)

    def enter(self, phase: str) -> "SurveyCheckpoint":
        """Record entry into a phase (idempotent re-entries increment
        the counter — that asymmetry is the resume evidence)."""
        if _PROTO_TRACE:
            self._proto_event("enter")
        self.phase = phase
        self.phase_entries[phase] = self.phase_entries.get(phase, 0) + 1
        return self

    def to_bytes(self) -> bytes:
        return json.dumps(dataclasses.asdict(self),
                          sort_keys=True).encode()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "SurveyCheckpoint":
        return cls(**json.loads(raw.decode()))

    def save(self, db: "ProofDB | None") -> None:
        if _PROTO_TRACE:
            self._proto_event("save")
        if db is not None:
            db.put(_CKPT_PREFIX + self.survey_id.encode(),
                   self.to_bytes())

    @classmethod
    def load(cls, db: "ProofDB | None",
             survey_id: str) -> "SurveyCheckpoint | None":
        if db is None:
            return None
        raw = db.get(_CKPT_PREFIX + survey_id.encode())
        if not raw:
            return None
        ck = cls.from_bytes(raw)
        if _PROTO_TRACE:
            ck._proto_event("load")
        return ck


__all__ = ["ProofDB", "SurveyCheckpoint", "pane_key"]

"""An on-disk store of compiled executables, keyed without tracing.

jax's persistent compilation cache is looked up AFTER a program has been
traced and lowered, and for the trace-time-unrolled limb kernels that is
minutes a process (utils/cache.py). This store is looked up BEFORE: a
call of a stored program with concrete arrays makes a key from the call's
abstract arguments and from everything else that can change the
executable, and a hit is `deserialize_and_load`ed and called. Nothing is
traced, lowered or compiled. A miss does what a plain `jit` call does (it
is one, and still goes through jax's cache); the executable that call
compiled is then serialised and written.

Where: `<the compile cache's directory>/exec_store/<program>-<key>.exe`,
one file per program and shape. To clear it, delete that sub-directory.

When: only where a persistent cache directory is configured and the
default backend is a TPU (`active`). Elsewhere a `StoredProgram` is the
plain jit it wraps.

What is stored is what jax's own cache stores: a serialised PjRt
executable (`jax.experimental.serialize_executable`), pickled beside the
argument and result trees (read back through proofs/safe_pickle.py) and
compressed as jax's cache compresses. Only files this program wrote are
unpickled.

The key (`key_of`) holds the program's name, every argument's shape, dtype
and weak type as `jit` sees them, and `process_facts()`: a digest of the
package's source by path relative to the package (so the store hits from
any checkout of the same source, and never across an edit), the versions
of jax, jaxlib and the backend, the device's kind, `jax_enable_x64`,
`XLA_FLAGS`, `LIBTPU_INIT_ARGS`; the program adds the devices it runs on
and what its trace `reads` of the environment and of module globals.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import secrets
import zlib
from typing import Callable, Mapping, Optional

try:
    import zstandard
except ImportError:         # jax's own cache falls back the same way
    zstandard = None

from ..proofs.safe_pickle import safe_loads
from ..resilience.policy import named_lock
from . import log
from .timers import PROCESS

SUBDIR = "exec_store"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def source_digest(package_dir: str = _PACKAGE) -> str:
    """sha256 over every `.py` file under `package_dir`: its path relative
    to that directory, then its bytes, in sorted order."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(package_dir):
        dirs.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, package_dir).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read() + b"\0")
    return h.hexdigest()


@functools.cache
def process_facts() -> Mapping:
    """What is the same for every program of this process and can change an
    executable. Read once (the source as this process imported it), and one
    mapping for every caller: copy it, do not change it."""
    import jax
    import jaxlib

    device = jax.devices()[0]
    return dict(
        source=source_digest(), jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        platform_version=device.client.platform_version,
        device_kind=device.device_kind,
        x64=bool(jax.config.jax_enable_x64),
        XLA_FLAGS=os.environ.get("XLA_FLAGS", ""),
        LIBTPU_INIT_ARGS=os.environ.get("LIBTPU_INIT_ARGS", ""))


def avals_of(args) -> tuple:
    """(tree, ((shape, dtype, weak type), ...)) of a call's arguments, as
    `jit` sees them; nothing is traced."""
    import jax

    leaves, tree = jax.tree.flatten(args)
    avals = tuple(jax.typeof(x) for x in leaves)
    return str(tree), tuple((a.shape, a.dtype.name, bool(a.weak_type))
                            for a in avals)


def key_of(program: str, avals, facts: Mapping) -> str:
    text = json.dumps([program, avals, sorted(facts.items())], default=str)
    return hashlib.sha256(text.encode()).hexdigest()[:32]


def _compress(blob: bytes) -> bytes:
    if zstandard:
        return zstandard.ZstdCompressor().compress(blob)
    return zlib.compress(blob, 1)


def _decompress(blob: bytes) -> bytes:
    if zstandard:
        return zstandard.ZstdDecompressor().decompress(blob)
    return zlib.decompress(blob)


def _write_atomic(path: str, blob: bytes) -> None:
    """Temporary file, fsync, rename: no partial file is ever visible under
    the final name. Concurrent writers of one key write the same bytes."""
    tmp = f"{path}.{secrets.token_hex(8)}.tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class ExecStore:
    """The store under one directory, and the process's table of what it
    has loaded or compiled. One lock per key: concurrent callers of one
    program and shape load or compile once."""

    def __init__(self, directory: str):
        self.directory = directory
        self._lock = named_lock("exec_store_table_lock")
        self._ready: dict = {}          # key -> jax.stages.Compiled
        self._key_locks: dict = {}

    def path(self, program: str, key: str) -> str:
        return os.path.join(self.directory, f"{program}-{key}.exe")

    def ready(self, key: str):
        """The executable this process already holds, or None."""
        return self._ready.get(key)

    def lock(self, key: str):
        with self._lock:
            return self._key_locks.setdefault(
                key, named_lock("exec_store_key_lock"))

    def load(self, program: str, key: str, args):
        """One look-up on disk, under the key's lock: the entry loaded and
        kept, or None. A bad entry (truncated, unreadable, refused) is
        counted, logged and deleted, and reads as a miss: the same program
        is then compiled for the same chip."""
        from jax.experimental import serialize_executable

        PROCESS.count("exec_store_lookups")
        path = self.path(program, key)
        if not os.path.exists(path):
            return None
        with PROCESS.step(f"setup/exec_store/load:{program}"):
            try:
                with open(path, "rb") as f:
                    payload, in_tree, out_tree = safe_loads(
                        _decompress(f.read()))
                found = serialize_executable.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=devices_of(args))
            except Exception as e:
                PROCESS.count("exec_store_load_failures")
                log.warn(f"exec store: bad entry {program}-{key} "
                         f"({type(e).__name__}: {e}); deleted, compiling")
                os.unlink(path)
                return None
        PROCESS.count("exec_store_hits")
        self._ready[key] = found
        return found

    def save(self, program: str, key: str, compiled) -> None:
        """Serialise, write and keep what a miss compiled."""
        from jax.experimental import serialize_executable

        os.makedirs(self.directory, exist_ok=True)
        _write_atomic(self.path(program, key), _compress(pickle.dumps(
            serialize_executable.serialize(compiled))))
        self._ready[key] = compiled


_STORES: dict = {}
_STORES_LOCK = named_lock("exec_store_stores_lock")


def active() -> Optional[ExecStore]:
    """The process's store where it engages, else None: a persistent cache
    directory is configured (utils/cache.py) and the default backend is a
    TPU."""
    import jax

    root = jax.config.jax_compilation_cache_dir
    if not root or jax.default_backend() != "tpu":
        return None
    with _STORES_LOCK:
        store = _STORES.get(root)
        if store is None:
            store = _STORES[root] = ExecStore(os.path.join(root, SUBDIR))
        return store


class StoredProgram:
    """A module-level `jax.jit` of arrays whose executables the store keeps.
    `reads()` gives what the program's trace reads beside its arguments and
    the source: environment variables and module globals, by name."""

    def __init__(self, fn, reads: Callable[[], Mapping]):
        self.jit, self.reads = fn, reads
        self.__name__ = self.program = fn.__name__
        self.lower = fn.lower

    def key(self, args) -> str:
        return key_of(self.program, avals_of(args), dict(
            process_facts(), devices=[d.id for d in devices_of(args)],
            **self.reads()))

    def __call__(self, *args):
        store = active()
        if store is None or not _concrete(args):
            return self.jit(*args)
        key = self.key(args)
        found = store.ready(key)
        if found is None:
            with store.lock(key):
                found = store.ready(key)
                if found is None:
                    found = store.load(self.program, key, args)
                if found is None:
                    # a miss: what a plain jit call does, and from this
                    # frame (what a trace costs swings with the frames
                    # above it: PERF.md, Open question 2) ...
                    with PROCESS.step(
                            f"setup/exec_store/compile:{self.program}"):
                        out = self.jit(*args)
                    # ... whose trace, module and executable jit's own
                    # caches then hand back: nothing is done twice
                    with PROCESS.step(
                            f"setup/exec_store/save:{self.program}"):
                        store.save(self.program, key,
                                   self.jit.lower(*args).compile())
                    return out
        return found(*args)


def trace_reads() -> dict:
    """What the trace of the program's stored G1 programs
    (service._fused_enc/_agg/_ks/_dec, parallel/dro.PROGRAMS,
    parallel/obfuscation.PROGRAMS) reads beside
    its arguments and the package's source: their keys hold it.
    `po.available()` reads DRYNX_NO_PALLAS and INTERPRET, the kernels'
    wrappers pass INTERPRET and `field.UNROLL` (DRYNX_FIELD_UNROLL) on as
    static arguments, DRYNX_BUCKET_TILE sets the tile that `_fused_enc` is
    called at. `eg.BASE_TABLE.table`, closed over by enc and ks, is a
    function of the source; the key tables, the secrets and the
    discrete-log table are arguments."""
    from ..crypto import field
    from ..crypto import pallas_ops as po
    from ..crypto import pallas_pairing as pp
    from ..encoding import tiles as enc_tiles

    return {"DRYNX_NO_PALLAS": os.environ.get("DRYNX_NO_PALLAS", "0"),
            enc_tiles.ENV_TILE: os.environ.get(enc_tiles.ENV_TILE, ""),
            "field.UNROLL": field.UNROLL,
            "pallas_ops.INTERPRET": po.INTERPRET,
            "pallas_pairing.INTERPRET": pp.INTERPRET}


def stored(fn) -> StoredProgram:
    """`fn`, a module-level jit of the G1 kernels, kept in the store."""
    return StoredProgram(fn, trace_reads)


def _concrete(args) -> bool:
    import jax

    return not any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree.leaves(args))


def devices_of(args) -> list:
    """The devices `jit` would run on: those the arguments are committed to,
    else the first."""
    import jax

    found = {d for x in jax.tree.leaves(args)
             if getattr(x, "committed", False) for d in x.devices()}
    return sorted(found, key=lambda d: d.id) or [jax.devices()[0]]


__all__ = ["ExecStore", "StoredProgram", "active", "avals_of", "key_of",
           "devices_of", "process_facts", "source_digest", "SUBDIR",
           "trace_reads", "stored"]

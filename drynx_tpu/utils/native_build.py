"""Shared build-on-demand protocol for the native C++ libraries.

One copy of the concurrent-build rules used by every ctypes binding
(crypto/native_pairing.py, service/store.py):
  * staleness = sha256 of (compiler flags, the host CPU's identity, every
    source file's bytes) in a stamp file next to the .so — so a flag
    change, or a tree copied to a machine with another CPU (the flags
    include -march=native), rebuilds; a bare mtime check misses both;
  * compile to a per-pid temp name and os.replace into place — parallel
    test processes (per-file isolation) may all build at once, and none
    may ever dlopen a half-written ELF;
  * CalledProcessError propagates with stderr attached (callers decide
    whether a missing toolchain is fatal).
"""
from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import warnings

FLAGS = ["-O3", "-march=native", "-funroll-loops",
         "-shared", "-fPIC", "-std=c++17"]


def _cpu_identity() -> str:
    """What -march=native compiles for: the CPU's model and feature flags
    as the kernel reports them. A library stamped on one CPU must never be
    dlopen'ed on another (SIGILL at the first unsupported instruction)."""
    ident = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            ident += sorted({ln.strip() for ln in f if ln.startswith(
                ("model name", "flags", "Features"))})
    except OSError:
        ident.append(platform.processor())
    return "\n".join(ident)


def build_native_lib(srcs: list[str], lib_path: str,
                     flags: list[str] | None = None) -> str:
    """Ensure lib_path is an up-to-date build of srcs; returns lib_path.
    srcs[0] is the translation unit; the rest (headers) only feed the
    staleness hash."""
    flags = FLAGS if flags is None else flags
    h = hashlib.sha256(" ".join(flags).encode())
    h.update(_cpu_identity().encode())
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()

    stamp = lib_path + ".stamp"
    if os.path.exists(lib_path) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read().strip() == digest:
                return lib_path

    os.makedirs(os.path.dirname(lib_path), exist_ok=True)
    tmp = f"{lib_path}.tmp.{os.getpid()}"
    try:
        # compile-once-others-wait IS the point of the build lock the
        # callers hold
        subprocess.run(  # drynx: noqa[blocking-call-under-lock]
            ["g++", *flags, srcs[0], "-o", tmp],
            check=True, capture_output=True, text=True)
        os.replace(tmp, lib_path)
        with open(stamp + f".tmp.{os.getpid()}", "w") as f:
            f.write(digest)
        os.replace(stamp + f".tmp.{os.getpid()}", stamp)
    finally:
        for t in (tmp, stamp + f".tmp.{os.getpid()}"):
            if os.path.exists(t):
                os.unlink(t)
    return lib_path


def warn_unavailable(what: str, e: Exception, fallback: str) -> None:
    """The LOUD fallback every binding uses when its library cannot be
    built or loaded: a silent flip to the slow path would also skip the
    parity tests that are skipif-unavailable."""
    detail = ""
    if isinstance(e, subprocess.CalledProcessError):
        detail = (e.stderr or "")[-500:]
    warnings.warn(f"{what} unavailable ({e!r}) {detail} — "
                  f"falling back to {fallback}")


__all__ = ["FLAGS", "build_native_lib", "warn_unavailable"]

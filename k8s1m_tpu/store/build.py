"""Build the native memstore shared library (g++; no pip deps).

The reference ships mem_etcd as a Rust crate built by cargo
(reference mem_etcd/Cargo.toml); here the native store is C++17 compiled
on demand into the package directory.  Import-time auto-build keeps the
test suite and the driver self-contained.

The library is rebuilt when the content hash of the ``native/`` sources
plus the compile command differs from the one recorded beside it
(``libmemstore.so.sha256``) — never on mtimes, which a copied or
freshly checked-out tree resets: a library left over from other sources
must not be loaded just because the copy made it look new.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(_PKG_DIR)), "native")
_SRC_DIRS = (
    os.path.join(_NATIVE_DIR, "memstore"),
    os.path.join(_NATIVE_DIR, "wirefront"),
)
LIB_PATH = os.path.join(_PKG_DIR, "libmemstore.so")
STAMP_PATH = LIB_PATH + ".sha256"
_FLAGS = ("g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread", "-Wall")
_UNITS = ("memstore/memstore.cc", "wirefront/wirefront.cc")

_lock = threading.Lock()


def source_hash() -> str:
    """sha256 over the compile command and every native source file
    (names and bytes) — what the built library is a function of."""
    h = hashlib.sha256()
    h.update("\0".join(_FLAGS + _UNITS).encode())
    for d in _SRC_DIRS:
        for name in sorted(os.listdir(d)):
            if name.endswith((".cc", ".h", ".inc")):
                h.update(f"\0{os.path.basename(d)}/{name}\0".encode())
                with open(os.path.join(d, name), "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def _stale(want: str) -> bool:
    if not (os.path.exists(LIB_PATH) and os.path.exists(STAMP_PATH)):
        return True
    with open(STAMP_PATH) as f:
        return f.read().strip() != want


def ensure_built(force: bool = False) -> str:
    """Compile libmemstore.so if missing or built from other sources;
    returns its path.

    One shared object holds both the store (native/memstore) and the
    per-RPC wire front-end (native/wirefront) so the wf_* entry points
    operate on the same ms_store the ctypes bindings hold.
    """
    with _lock:
        want = source_hash()
        if not force and not _stale(want):
            return LIB_PATH
        # Per-PID tmp: concurrent builds (many freshly spawned harness
        # subprocesses seeing a stale lib at once) must not clobber each
        # other's half-written output before the atomic replace.
        tmp = f"{LIB_PATH}.{os.getpid()}.tmp"
        cmd = [
            *_FLAGS, "-o", tmp,
            *(os.path.join(_NATIVE_DIR, u) for u in _UNITS),
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except FileNotFoundError as e:
            raise RuntimeError(
                "g++ not found: the native store (libmemstore.so) is built "
                f"on demand from {_NATIVE_DIR} and is not committed"
            ) from e
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native store build failed (rc={e.returncode}):\n{e.stderr}"
            ) from e
        os.replace(tmp, LIB_PATH)
        stamp_tmp = f"{STAMP_PATH}.{os.getpid()}.tmp"
        with open(stamp_tmp, "w") as f:
            f.write(want + "\n")
        os.replace(stamp_tmp, STAMP_PATH)
        return LIB_PATH


if __name__ == "__main__":
    print(ensure_built(force=True))

"""Lazy build of the native object store shared library.

The reference ships prebuilt bazel binaries (src/ray/object_manager/plasma);
here we compile on first import and cache next to the source (git-ignored:
no binary is committed, a fresh checkout builds its own). g++ is part of
the installation; the build takes <2s.

Sanitizer mode (the reference runs its C++ store tests under ASan/TSan in
CI): set ``RTPU_OBJSTORE_SANITIZE=address,undefined`` (any comma-joined
``-fsanitize=`` list) and every process that builds/loads the store in that
environment gets a ``libobjstore.<mode>.so`` debug build (-O1 -g, frame
pointers) instead of the production one. The sanitized variant caches
under its own name + source-hash file, so flipping the env never clobbers
the production binary. Loading an ASan build into a non-instrumented
python requires LD_PRELOADing libasan/libubsan — tests/test_sanitizers.py
shows the full recipe.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "objstore.cc")
_lock = threading.Lock()


def _san_mode() -> str:
    """Normalized sanitizer list from the env ('' = production build)."""
    mode = os.environ.get("RTPU_OBJSTORE_SANITIZE", "").strip()
    return ",".join(s.strip() for s in mode.split(",") if s.strip())


def _lib_path(mode: str) -> str:
    if not mode:
        return os.path.join(_DIR, "libobjstore.so")
    tag = mode.replace(",", "-")
    return os.path.join(_DIR, f"libobjstore.{tag}.so")


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _compile_and_swap(mode: str) -> None:
    """Compile to a tmp path and atomically replace the .so + hash.
    Caller holds _lock. Raises CalledProcessError on compile errors and
    OSError when the compiler is missing / checkout is read-only."""
    lib = _lib_path(mode)
    # per-process name: two processes building at once (a driver and a
    # node agent on a fresh checkout) must not write through each other
    tmp = f"{lib}.{os.getpid()}.tmp"
    if mode:
        # debug-grade opt level + frame pointers: sanitizer reports with
        # usable stacks beat a fast binary nobody profiles
        flags = [f"-fsanitize={mode}", "-O1", "-g",
                 "-fno-omit-frame-pointer"]
    else:
        flags = ["-O2", "-g"]
    subprocess.run(
        ["g++", *flags, "-shared", "-fPIC", "-std=c++17",
         "-o", tmp, _SRC, "-lpthread"],
        check=True,
        capture_output=True,
    )
    os.replace(tmp, lib)
    with open(lib + ".srchash", "w") as f:
        f.write(_src_hash())


def ensure_built() -> str:
    """Compile objstore.cc -> libobjstore[.<san>].so if missing or stale.

    Staleness is a CONTENT hash of the source, not mtimes: a fresh git
    checkout gives every file the same mtime, which let a committed .so
    shadow newer committed source (missing-symbol crashes at import).
    """
    mode = _san_mode()
    lib = _lib_path(mode)
    with _lock:
        want = _src_hash()
        have = None
        if os.path.exists(lib) and os.path.exists(lib + ".srchash"):
            try:
                with open(lib + ".srchash") as f:
                    have = f.read().strip()
            except OSError:
                pass
        if have != want:
            try:
                _compile_and_swap(mode)
            except subprocess.CalledProcessError as e:
                # a real compile error must surface (silently loading the
                # stale .so is the failure mode this hash scheme prevents)
                raise RuntimeError(
                    "objstore.cc failed to compile:\n"
                    + e.stderr.decode(errors="replace")) from e
            except OSError:
                # no compiler / read-only checkout: a .so built earlier is
                # still usable (it may just predate the latest source). A
                # sanitizer build with no compiler has nothing to fall
                # back to.
                if mode or not os.path.exists(lib):
                    raise
    return lib


def rebuild() -> str:
    """Recompile for THIS host and swap in the result. Used when a
    shipped binary fails to LOAD (e.g. built against a newer glibc than
    this host) — the content hash can't catch that, only dlopen can.
    The existing .so is replaced only AFTER a successful compile: a
    compiler-less host, or a checkout shared over NFS with hosts where
    the shipped binary loads fine, must never lose it to a failed
    attempt."""
    mode = _san_mode()
    with _lock:
        try:
            _compile_and_swap(mode)
        except (subprocess.CalledProcessError, OSError) as e:
            stderr = getattr(e, "stderr", None) or b""
            raise RuntimeError(
                "libobjstore.so failed to load and recompiling for this "
                "host failed:\n" + stderr.decode(errors="replace")) from e
    return _lib_path(mode)

"""Build the native collective engine (libhvdtpu.so).

Counterpart of the reference's setup.py extension build
(/root/reference/setup.py:31-34,210-425), radically simplified: no MPI/CUDA/
NCCL feature probing is needed because the engine's only system dependencies
are POSIX sockets and pthreads.  The library is compiled on first import and
cached next to the sources; rebuilt when any source is newer than the binary.

Sanitized builds (docs/contributing.md#sanitized-engine-builds):
``HVD_TPU_SANITIZE=thread|address|undefined`` compiles the engine with the
matching ``-fsanitize=`` runtime into its own ``libhvdtpu.<mode>.so`` next
to the normal binary, so switching modes never invalidates the regular
cached build.  Loading a sanitized engine into an uninstrumented python
needs the sanitizer runtime preloaded — ``sanitizer_preload()`` returns
the ``LD_PRELOAD`` path (the slow-tier TSan test in tests/test_sanitize.py
wires this for its rank subprocesses).
"""

from __future__ import annotations

import functools
import hashlib
import os
import shutil
import subprocess
import tempfile
from typing import List, Optional

_CC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cc")
_SOURCES = ["net.cc", "transport.cc", "wire.cc", "timeline.cc", "autotune.cc", "flight.cc",
            "engine.cc", "simscale.cc", "c_api.cc"]
_LIB_NAME = "libhvdtpu.so"

# -O3 + native SIMD for the AccumulateSum / half-conversion hot loops.
# -march=native is safe *only* because the build stamp below keys the
# cached .so on the host's CPU feature set: a package directory shared
# over NFS or baked into an image rebuilds on a host whose ISA differs
# instead of SIGILL-ing on unsupported instructions.
_FLAGS = ["-std=c++17", "-O3", "-march=native", "-g", "-fPIC", "-shared",
          "-pthread", "-Wall", "-Wextra", "-Wno-unused-parameter"]

# Sanitizer modes -> (compile flags, runtime to preload into
# uninstrumented hosts).  ONE table so a future mode cannot be accepted
# by the build but unknown to the preload resolver (or vice versa).
# Flags swap in for the -O3/-march pair (-O1 + frame pointers keep
# reports readable and the instrumented hot loops tolerable; correctness
# tools don't want vectorized shuffles anyway).
_SANITIZERS = {
    "thread": (["-fsanitize=thread"], "libtsan.so"),
    "address": (["-fsanitize=address"], "libasan.so"),
    "undefined": (["-fsanitize=undefined", "-fno-sanitize-recover=all"],
                  "libubsan.so"),
}


def sanitize_mode() -> str:
    """The validated ``HVD_TPU_SANITIZE`` mode ('' = normal build)."""
    mode = (os.environ.get("HVD_TPU_SANITIZE") or "").strip().lower()
    _check_mode(mode)
    return mode


def _check_mode(mode: str) -> None:
    if mode and mode not in _SANITIZERS:
        raise ValueError(
            f"HVD_TPU_SANITIZE: unknown sanitizer {mode!r} "
            f"(want {', '.join(sorted(_SANITIZERS))})")


def _flags(mode: str) -> List[str]:
    if not mode:
        return list(_FLAGS)
    base = [f for f in _FLAGS if f not in ("-O3", "-march=native")]
    return base + ["-O1", "-fno-omit-frame-pointer"] + _SANITIZERS[mode][0]


def lib_path(mode: Optional[str] = None) -> str:
    if mode is None:
        mode = sanitize_mode()
    name = _LIB_NAME if not mode else f"libhvdtpu.{mode}.so"
    return os.path.join(_CC_DIR, name)


def sanitizer_preload(mode: Optional[str] = None) -> str:
    """Path of the sanitizer runtime to LD_PRELOAD when dlopen-ing a
    sanitized engine from an uninstrumented python ('' for normal
    builds, or when the compiler can't name it).  Raises ``ValueError``
    on an unknown mode, like :func:`sanitize_mode`."""
    if mode is None:
        mode = sanitize_mode()
    if not mode:
        return ""
    _check_mode(mode)
    return _resolve_preload(mode)


@functools.lru_cache(maxsize=None)
def _resolve_preload(mode: str) -> str:
    """One compiler subprocess per mode per process: the launcher calls
    sanitizer_preload once per rank, and the answer never changes."""
    cxx = os.environ.get("CXX", "g++")
    try:
        out = subprocess.run(
            [cxx, f"-print-file-name={_SANITIZERS[mode][1]}"],
            capture_output=True, text=True, timeout=30).stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        return ""
    # An unresolved -print-file-name echoes the bare name back.
    if not out or os.sep not in out:
        return ""
    real = os.path.realpath(out)
    return real if os.path.exists(real) else ""


def _stamp_path(mode: str = "") -> str:
    suffix = f".{mode}" if mode else ""
    return os.path.join(_CC_DIR, f".buildstamp{suffix}")


def _build_stamp(mode: str = "") -> str:
    """Fingerprint of everything that must invalidate the cached binary
    besides source mtimes: the compile flags and the host CPU's ISA."""
    cpu = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    cpu = line
                    break
    except OSError:
        pass
    payload = " ".join(_flags(mode)) + " -lrt" + "|" + cpu
    return hashlib.sha256(payload.encode()).hexdigest()


def needs_build(mode: Optional[str] = None) -> bool:
    if mode is None:
        mode = sanitize_mode()
    lib = lib_path(mode)
    if not os.path.exists(lib):
        return True
    try:
        with open(_stamp_path(mode)) as f:
            if f.read().strip() != _build_stamp(mode):
                return True
    except OSError:
        return True
    lib_mtime = os.path.getmtime(lib)
    for fname in os.listdir(_CC_DIR):
        if fname.endswith((".cc", ".h")):
            if os.path.getmtime(os.path.join(_CC_DIR, fname)) > lib_mtime:
                return True
    return False


def _sweep_stale_tmp() -> None:
    """Remove build droppings an earlier interrupted build left next to
    the sources: tmp*.so from the pre-temp-dir scheme (SIGKILL — e.g. the
    launcher's kill cascade — mid-compile leaked the mkstemp file), and
    stage_*.so.part from a kill during the staging copy.  Staging files
    are age-gated: a young one may belong to a CONCURRENT builder
    mid-copy and must not be unlinked from under it."""
    import time

    try:
        for fname in os.listdir(_CC_DIR):
            path = os.path.join(_CC_DIR, fname)
            stale = fname.startswith("tmp") and (
                fname.endswith(".so") or fname.endswith(".so.part"))
            if fname.startswith("stage_") and fname.endswith(".so.part"):
                try:
                    stale = time.time() - os.path.getmtime(path) > 300
                except OSError:
                    stale = False
            if stale:
                try:
                    os.unlink(path)
                except OSError:
                    pass
    except OSError:
        pass


def build(verbose: bool = False, force: bool = False) -> str:
    """Compile the engine; returns the .so path.  Raises on failure.
    ``HVD_TPU_SANITIZE`` selects a sanitized variant (own lib name, own
    stamp — the normal cached build is never invalidated by it).
    ``force`` compiles from the sources whatever binary and stamp lie
    beside them (chip_smoke.py: the binary is not a committed file, so a
    proof that the tree builds must not lean on one)."""
    mode = sanitize_mode()
    lib = lib_path(mode)
    if not force and not needs_build(mode):
        return lib
    _sweep_stale_tmp()
    cxx = os.environ.get("CXX", "g++")
    srcs = [os.path.join(_CC_DIR, s) for s in _SOURCES]
    # Compile in a throwaway temp DIRECTORY (system tmp, not the package
    # tree): a process killed mid-compile — the common leak source was the
    # launcher's kill cascade landing during a ~10 s rebuild — can no
    # longer strand tmp*.so files next to the sources.  The finished
    # binary is then staged next to the target and atomically renamed, so
    # concurrent test processes racing to build don't load a half-written
    # .so; the staging window is a few ms of copy, not the whole compile.
    tmpdir = tempfile.mkdtemp(prefix="hvdtpu_build_")
    stage = None
    try:
        out = os.path.join(tmpdir, os.path.basename(lib))
        # -lrt after the sources: shm_open/shm_unlink live in librt on
        # glibc < 2.34 (newer glibc keeps them in libc and the flag is a
        # harmless no-op).
        cmd = [cxx] + _flags(mode) + ["-o", out] + srcs + ["-lrt"]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"failed to build {os.path.basename(lib)}:\n{proc.stderr}")
        # prefix "stage_", NOT the mkstemp default "tmp": _sweep_stale_tmp
        # matches tmp* and must never unlink a CONCURRENT builder's live
        # staging file mid-copy.
        fd, stage = tempfile.mkstemp(prefix="stage_", suffix=".so.part",
                                     dir=_CC_DIR)
        os.close(fd)
        shutil.copy(out, stage)  # tmpdir may be another filesystem
        os.replace(stage, lib)
        stage = None
        with open(_stamp_path(mode), "w") as f:
            f.write(_build_stamp(mode))
    finally:
        if stage is not None and os.path.exists(stage):
            os.unlink(stage)
        shutil.rmtree(tmpdir, ignore_errors=True)
    if verbose:
        print(f"[horovod_tpu] built {lib}")
    return lib


if __name__ == "__main__":
    build(verbose=True)

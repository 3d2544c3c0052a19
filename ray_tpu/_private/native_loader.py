"""Build + load the native (C++) runtime components.

The shared library compiles on first use (g++ -O3 -shared) and is
cached under ``native/build/`` keyed by everything the binary depends
on — sources, compiler flags and, because of ``-march=native``, the
host CPU — so a fresh checkout needs no explicit build step, and a
binary built for another commit or another machine can't load.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")

_lock = threading.Lock()
_cache = {}


def _host_cpu() -> bytes:
    """What ``-march=native`` resolves against: the machine type plus
    the first core's model and feature flags."""
    ident = [platform.machine().encode()]
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"model name", b"flags", b"Features")):
                    ident.append(line)
                elif not line.strip():
                    break               # end of the first core's block
    except OSError:
        pass    # no procfs: the machine type alone keys the build
    return b"".join(ident)


def _build_hash(paths, flags) -> str:
    h = hashlib.sha1()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(" ".join(flags).encode())
    h.update(_host_cpu())
    return h.hexdigest()[:16]


def load_library(name: str, sources, extra_flags=()) -> Optional[
        ctypes.CDLL]:
    """Compile (if needed) and dlopen native/<name>; None on failure."""
    with _lock:
        if name in _cache:
            return _cache[name]
        try:
            srcs = [os.path.join(_NATIVE_DIR, s) for s in sources]
            build_dir = os.path.join(_NATIVE_DIR, "build")
            os.makedirs(build_dir, exist_ok=True)
            flags = ["-O3", "-march=native", "-std=c++17", "-shared",
                     "-fPIC", *extra_flags]
            tag = _build_hash(srcs, flags)
            so_path = os.path.join(build_dir, f"{name}-{tag}.so")
            if not os.path.exists(so_path):
                cmd = ["g++", *flags, *srcs, "-o", so_path + ".tmp"]
                # blocking-ok: one-time compile at first use; the lock
                # IS the build serialization — concurrent callers must
                # wait for the single .so rather than race the compiler
                subprocess.run(cmd, check=True, capture_output=True,
                               cwd=_NATIVE_DIR)
                os.rename(so_path + ".tmp", so_path)
            lib = ctypes.CDLL(so_path)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", b"")
            logger.warning("native %s unavailable (%s) %s", name, e,
                           detail.decode()[:500] if detail else "")
            lib = None
        _cache[name] = lib
        return lib


def scheduler_lib() -> Optional[ctypes.CDLL]:
    lib = load_library("rtpu_scheduler", ["scheduler.cc"])
    if lib is not None and not getattr(lib, "_rtpu_typed", False):
        import ctypes as ct
        f32p = ct.POINTER(ct.c_float)
        u8p = ct.POINTER(ct.c_uint8)
        i32p = ct.POINTER(ct.c_int32)
        lib.rtpu_hybrid_schedule.argtypes = [
            f32p, f32p, u8p, ct.c_int, ct.c_int, f32p, i32p, ct.c_int,
            ct.c_float, ct.c_int, ct.c_float, ct.c_uint64, i32p, u8p]
        lib.rtpu_hybrid_schedule.restype = None
        lib.rtpu_hybrid_schedule_classes.argtypes = [
            f32p, f32p, u8p, ct.c_int, ct.c_int, f32p, i32p, i32p,
            ct.c_int, ct.c_float, i32p]
        lib.rtpu_hybrid_schedule_classes.restype = None
        lib._rtpu_typed = True
    return lib

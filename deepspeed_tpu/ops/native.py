"""Native host library loader (role of the reference's OpBuilder JIT path,
op_builder/builder.py:108 ``OpBuilder.load`` — compile-on-first-use with a
cached artifact; here g++ → shared object consumed over ctypes instead of a
torch extension).

Builds ``csrc/host_ops.cpp`` (vectorized host optimizers + AIO threadpool)
into ``build/libds_host_ops.<key>.so`` on first use, where ``<key>`` hashes
the source text, the compiler flags and this host's CPU identification —
``-march=native`` bakes the build machine's instruction set into the
object, and ``build/`` can travel with a copied tree, so an artefact made
from other source, other flags or on another CPU is never loaded.
``available()`` gates the callers; everything has a numpy fallback so the
framework works without a toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "csrc", "host_ops.cpp")
_BUILD_DIR = os.environ.get(
    "DS_BUILD_DIR", os.path.join(_REPO_ROOT, "build"))
_BASE_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-fopenmp"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_i64 = ctypes.c_int64
_f32p = ctypes.POINTER(ctypes.c_float)


def _host_cpu_id() -> str:
    """What ``-march=native`` depends on: the CPU model and its feature
    flags (first processor entry of /proc/cpuinfo), else the platform's
    own description."""
    try:
        with open("/proc/cpuinfo") as f:
            seen = {}
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen[key] = line.strip()
            if seen:
                return platform.machine() + "|" + "|".join(
                    seen[k] for k in sorted(seen))
    except OSError:
        pass
    return platform.machine() + "|" + platform.processor()


def _lib_path(flags) -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(flags).encode())
    h.update(_host_cpu_id().encode())
    return os.path.join(_BUILD_DIR,
                        f"libds_host_ops.{h.hexdigest()[:16]}.so")


def _compile() -> Optional[str]:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # -march=native first; without it as the portable retry.  Each flag
    # set has its own artefact name, so a hit is always this source built
    # with these flags for this CPU.
    err = ""
    for flags in (_BASE_FLAGS + ["-march=native"], _BASE_FLAGS):
        lib = _lib_path(flags)
        if os.path.exists(lib):
            return lib
        # Build to a per-process temp path and rename atomically: N local
        # ranks may race here (the threading lock is per-process only),
        # and a concurrent truncate of a dlopen'd .so is a SIGBUS.
        tmp = f"{lib}.tmp.{os.getpid()}"
        cmd = ["g++", *flags, _SRC, "-o", tmp, "-lpthread"]
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=300)
        except (OSError, subprocess.TimeoutExpired) as e:  # no toolchain
            logger.warning(f"native host ops unavailable (g++ failed: {e})")
            return None
        if r.returncode == 0:
            os.replace(tmp, lib)
            logger.info(f"built native host ops -> {lib}")
            return lib
        err = r.stderr
    logger.warning(f"native host ops build failed:\n{err[-1000:]}")
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.ds_adam_step.argtypes = [
        _f32p, _f32p, _f32p, _f32p, _i64, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_int,
        ctypes.c_int, ctypes.c_int]
    lib.ds_lion_step.argtypes = [
        _f32p, _f32p, _f32p, _i64, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_float]
    lib.ds_adagrad_step.argtypes = [
        _f32p, _f32p, _f32p, _i64, ctypes.c_float, ctypes.c_float,
        ctypes.c_float]
    lib.ds_aio_new.argtypes = [ctypes.c_int, _i64]
    lib.ds_aio_new.restype = ctypes.c_void_p
    lib.ds_aio_free.argtypes = [ctypes.c_void_p]
    lib.ds_aio_pread.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_void_p, _i64, _i64]
    lib.ds_aio_pread.restype = _i64
    lib.ds_aio_pwrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_void_p, _i64, _i64]
    lib.ds_aio_pwrite.restype = _i64
    lib.ds_aio_wait.argtypes = [ctypes.c_void_p, _i64]
    lib.ds_aio_wait.restype = ctypes.c_int
    lib.ds_aio_wait_all.argtypes = [ctypes.c_void_p]
    lib.ds_aio_wait_all.restype = ctypes.c_int
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = _compile()
        if path is None:
            return None
        try:
            _lib = _bind(ctypes.CDLL(path))
        except OSError as e:
            logger.warning(f"native host ops load failed: {e}")
            _lib = None
        return _lib


def available() -> bool:
    return get_lib() is not None

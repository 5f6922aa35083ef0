"""Native runtime bindings (C++ via ctypes — no pybind11 dependency).

The reference consumed all native code as prebuilt images (SURVEY.md §2.9);
tpustack's own native layer lives in ``native/`` and is loaded here.  Current
surface:

- ``png_encode(img)`` — zlib-backed RGB8 PNG writer used by the serving hot
  path (``tpustack.utils.image`` falls back to PIL when the library isn't
  built).

The shared object is built on first use (``make -C native``) by the machine
that runs it: a stamp beside it records the source it was built from and
the host that built it, and a ``.so`` whose stamp does not match — stale
source, or a leftover copied in from another machine (``native/*.so`` is
gitignored, so a copy of the working tree can carry one) — is rebuilt, never
trusted.  If the build fails the process serves with PIL and says so
(``encoder()``; the servers log it at start-up and report it on
``/healthz``).  Set ``TPUSTACK_NO_NATIVE=1`` to skip entirely.  Servers
should call ``available()`` once at startup so the (up to 120 s) build never
lands inside a request; ``_load`` is locked so concurrent first calls cannot
race two ``make`` processes against ``dlopen``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from tpustack.utils.logging import get_logger

log = get_logger("runtime")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libtpustack_runtime.so")
_STAMP_PATH = _SO_PATH + ".stamp"
_SOURCES = ("png_encoder.cc", "Makefile")

_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_load_lock = threading.Lock()


def _build_id() -> str:
    """What a trustworthy ``.so`` was built from and where: sha256 over the
    sources plus this host's identity (mtimes do not survive a copy)."""
    h = hashlib.sha256("|".join(os.uname()).encode())
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_here() -> bool:
    try:
        with open(_STAMP_PATH) as f:
            return os.path.exists(_SO_PATH) and f.read().strip() == _build_id()
    except OSError:
        return False


def _build() -> None:
    subprocess.run(["make", "-C", _NATIVE_DIR, "-B"], check=True,
                   capture_output=True, timeout=120)
    with open(_STAMP_PATH, "w") as f:
        f.write(_build_id())


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    from tpustack.utils import knobs

    if _load_failed or knobs.get_bool("TPUSTACK_NO_NATIVE"):
        return None
    with _load_lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            if not _built_here():
                # blocking build under the lock is the point: exactly one
                # thread pays the compile, every other caller waits for
                # the finished .so instead of racing a second make
                _build()
            lib = ctypes.CDLL(_SO_PATH)
        except (OSError, subprocess.SubprocessError) as e:
            _load_failed = True  # don't re-pay the failing build per call
            detail = (getattr(e, "stderr", b"") or b"").decode(
                "utf-8", "replace").strip()[-300:]
            log.warning("native runtime unavailable (%r %s) — PNG encoding "
                        "falls back to PIL", e, detail)
            return None
        lib.tpustack_png_encode.restype = ctypes.c_long
        lib.tpustack_png_encode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint8), ctypes.c_long,
        ]
        _lib = lib
        return lib


def available() -> bool:
    return _load() is not None


def encoder() -> str:
    """Which PNG encoder this process serves with: ``native`` or ``pil``."""
    return "native" if available() else "pil"


def png_encode(img: np.ndarray, compression: int = 6) -> bytes:
    """Encode ``[H, W, 3]`` uint8 (C-contiguous) as PNG bytes."""
    lib = _load()
    if lib is None:
        raise ImportError("native runtime not built (see native/Makefile)")
    img = np.ascontiguousarray(img)
    if img.dtype != np.uint8 or img.ndim != 3 or img.shape[2] != 3:
        raise ValueError(f"expected [H,W,3] uint8, got {img.shape} {img.dtype}")
    h, w = int(img.shape[0]), int(img.shape[1])
    # worst case: header + zlib bound (~raw + raw/1000 + 64) + chunk overhead
    cap = 8 + 25 + 12 + (3 * w + 1) * h + ((3 * w + 1) * h) // 500 + 1024 + 12
    out = (ctypes.c_uint8 * cap)()
    n = lib.tpustack_png_encode(
        img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w,
        compression, out, cap)
    if n <= 0:
        raise RuntimeError("native png_encode failed")
    return ctypes.string_at(out, n)

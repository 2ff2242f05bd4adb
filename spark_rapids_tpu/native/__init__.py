"""ctypes binding for the native host control-plane kernels.

The .so builds from srt_native.cpp on first import when a compiler is
available (build product is cached next to the source); every entry point
has a pure-Python fallback, so the framework works without a toolchain.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "srt_native.cpp")
_SO = os.path.join(_DIR, "_srt_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    import shutil

    gxx = shutil.which("g++") or shutil.which("clang++")
    if gxx is None:
        return False
    # build beside the target and rename: another process loading the
    # library never sees a half-written file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [gxx, "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except Exception as e:  # noqa: BLE001
        log.info("native build skipped: %s", e)
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None when
    unavailable (callers use their Python fallback)."""
    global _lib, _tried
    if _lib is not None:
        # per-page callers come here thousands of times a scan, from
        # every task thread: no lock once the library is loaded
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or \
                os.path.getmtime(_SO) < os.path.getmtime(_SRC):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.info("native load failed: %s", e)
            return None
        try:
            _bind(lib)
            # the per-page helpers run for microseconds and are called a
            # page at a time beside other Python threads: through PyDLL
            # they keep the interpreter's lock instead of handing it over
            # and queueing for it again at every call (the whole-chunk
            # and whole-file ones stay on CDLL and run beside Python)
            quick = ctypes.PyDLL(_SO)
            _bind(quick)
            for name in _PER_PAGE:
                setattr(lib, name, getattr(quick, name))
        except AttributeError as e:
            # stale cached .so predating a newly added symbol (mtime-equal
            # copies skip the rebuild): fall back to pure Python
            log.info("native lib stale (%s); using Python fallbacks", e)
            return None
        _lib = lib
        return _lib


_PER_PAGE = ("srt_parse_runs", "srt_parse_pages", "srt_plain_strings")


def _bind(lib) -> None:
    lib.srt_parse_runs.restype = ctypes.c_int64
    lib.srt_parse_runs.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.srt_parse_pages.restype = ctypes.c_int64
    lib.srt_parse_pages.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.srt_plain_strings.restype = ctypes.c_int64
    lib.srt_plain_strings.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.srt_snappy_pages.restype = ctypes.c_int64
    lib.srt_snappy_pages.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
    ]
    lib.srt_csv_plan.restype = ctypes.c_int64
    lib.srt_csv_plan.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint8,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
    ]

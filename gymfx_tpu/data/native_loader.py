"""ctypes bindings for the native CSV loader.

Replaces the pandas parse on the hot data path (the reference loads
with pd.read_csv — data_feed_plugins/default_data_feed.py:40) with the
C++ columnar parser.  Strictness contract: the native parser handles
the canonical bar schema (DATE_TIME,OPEN,HIGH,LOW,CLOSE,VOLUME with
fixed-format timestamps) and REFUSES anything else, in which case the
caller silently falls back to pandas — exotic files behave exactly as
before, canonical files load several times faster.

Set GYMFX_NATIVE_LOADER=0 to disable, =require to hard-fail when the
native path cannot serve a file (for tests/benchmarks).
"""
from __future__ import annotations

import ctypes
import os
import pathlib
from typing import Optional

import numpy as np
import pandas as pd

_lib: Optional[ctypes.CDLL] = None
_lib_failed = False
# path -> which loader served it last ("native", or "pandas: <why>");
# chip_smoke.py prints it so a silent fallback is at least visible
_served: dict = {}


def served_by(path) -> Optional[str]:
    """Which loader served ``path`` the last time it was loaded."""
    return _served.get(str(path))


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    try:
        import subprocess
        import sys

        build = pathlib.Path(__file__).resolve().parents[2] / "tools" / "build_native.py"
        # build_native names the library after the hash of the committed
        # csv_loader.cpp and builds it when absent (lock + atomic
        # rename), so it is safe and cheap to invoke every time; its
        # last line is "built <path>"
        done = subprocess.run([sys.executable, str(build)], check=True,
                              capture_output=True, text=True)
        lib = ctypes.CDLL(done.stdout.split()[-1])
        lib.gymfx_csv_parse.restype = ctypes.c_void_p
        lib.gymfx_csv_parse.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_int64)]
        lib.gymfx_csv_fill.restype = None
        lib.gymfx_csv_fill.argtypes = [ctypes.c_void_p] + [
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        ] + [np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")] * 5
        lib.gymfx_csv_free.restype = None
        lib.gymfx_csv_free.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception:
        _lib_failed = True
        _lib = None
    return _lib


def native_enabled() -> bool:
    return os.environ.get("GYMFX_NATIVE_LOADER", "1") != "0"


_CANONICAL = {"DATE_TIME", "OPEN", "HIGH", "LOW", "CLOSE", "VOLUME"}


def _header_is_canonical(path: str) -> bool:
    """Only the exact bar schema qualifies — files with extra engineered
    feature columns must go through pandas, which preserves them."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            header = fh.readline().strip()
    except OSError:
        return False
    cols = {c.strip().upper() for c in header.split(",")}
    # require the FULL schema: with any column absent, the pandas path's
    # price_column-driven backfill semantics apply and could diverge
    return cols == _CANONICAL


def load_ohlcv_csv(path: str) -> Optional[pd.DataFrame]:
    """Native parse -> dataframe with DatetimeIndex, or None when the
    file is not canonical / the library is unavailable."""
    if not native_enabled():
        _served[str(path)] = "pandas: GYMFX_NATIVE_LOADER=0"
        return None
    if not _header_is_canonical(path):
        if os.environ.get("GYMFX_NATIVE_LOADER") == "require":
            raise RuntimeError(f"native loader: non-canonical header in {path}")
        _served[str(path)] = "pandas: non-canonical header"
        return None
    lib = _load_lib()
    if lib is None:
        if os.environ.get("GYMFX_NATIVE_LOADER") == "require":
            raise RuntimeError("native loader required but unavailable")
        _served[str(path)] = "pandas: native library unavailable"
        return None
    n = ctypes.c_int64(0)
    handle = lib.gymfx_csv_parse(str(path).encode(), ctypes.byref(n))
    if not handle:
        if os.environ.get("GYMFX_NATIVE_LOADER") == "require":
            raise RuntimeError(f"native loader could not parse {path}")
        _served[str(path)] = "pandas: native parser refused the file"
        return None
    _served[str(path)] = "native"
    try:
        rows = int(n.value)
        epoch = np.empty(rows, np.int64)
        o = np.empty(rows, np.float64)
        h = np.empty(rows, np.float64)
        l = np.empty(rows, np.float64)
        c = np.empty(rows, np.float64)
        v = np.empty(rows, np.float64)
        lib.gymfx_csv_fill(handle, epoch, o, h, l, c, v)
    finally:
        lib.gymfx_csv_free(handle)
    index = pd.DatetimeIndex(epoch.view("datetime64[s]"), name="DATE_TIME")
    return pd.DataFrame(
        {"OPEN": o, "HIGH": h, "LOW": l, "CLOSE": c, "VOLUME": v}, index=index
    )

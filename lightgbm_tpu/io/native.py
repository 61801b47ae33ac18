"""ctypes bindings for the native (C++) host components.

The compute path is JAX/Pallas; the host runtime around it follows the
reference's native design where it matters — the text parser here mirrors
src/io/parser.cpp.  The shared library is built from native/ (see
native/Makefile); if it is missing, an on-demand g++ build is attempted
once, and every entry point degrades gracefully to the pure-Python
fallback so the package never hard-depends on a toolchain.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

from ..utils import log

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_NAME = "libtpugbdt_parser.so"

_lib = None
_lib_tried = False


def _build_lib() -> Optional[str]:
    src = os.path.join(_NATIVE_DIR, "fast_parser.cpp")
    out = os.path.join(_NATIVE_DIR, _LIB_NAME)
    if not os.path.exists(src):
        return None
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-o", out, src,
             "-lpthread"],
            check=True, capture_output=True, timeout=120)
        return out
    except Exception as e:  # toolchain absent / build error -> fallback
        log.debug("native parser build failed: %s", e)
        return None


def get_lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    path = os.path.join(_NATIVE_DIR, _LIB_NAME)
    src = os.path.join(_NATIVE_DIR, "fast_parser.cpp")
    # the library is git-ignored and built here on demand: one older than
    # its source is a stale leftover, not the code in the tree
    if not os.path.exists(path) or (
            os.path.exists(src)
            and os.path.getmtime(src) > os.path.getmtime(path)):
        path = _build_lib()
    if not path:
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.tpugbdt_parse_file.restype = ctypes.c_int
        lib.tpugbdt_parse_file.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.c_int)]
        lib.tpugbdt_free.restype = None
        lib.tpugbdt_free.argtypes = [ctypes.c_void_p]
        _lib = lib
    except OSError as e:
        log.debug("native parser load failed: %s", e)
        _lib = None
    return _lib


def parse_file(filename: str, header: bool = False,
               num_features_hint: int = 0
               ) -> Optional[Tuple[np.ndarray, Optional[np.ndarray], int]]:
    """(matrix, libsvm_labels_or_None, format 0=csv/1=tsv/2=libsvm), or
    None when the native library is unavailable or parsing failed."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    data_p = ctypes.POINTER(ctypes.c_double)()
    labels_p = ctypes.POINTER(ctypes.c_double)()
    fmt = ctypes.c_int()
    rc = lib.tpugbdt_parse_file(
        filename.encode(), int(header), 0, int(num_features_hint),
        ctypes.byref(rows), ctypes.byref(cols), ctypes.byref(data_p),
        ctypes.byref(labels_p), ctypes.byref(fmt))
    if rc != 0:
        return None
    n, c = rows.value, cols.value
    try:
        mat = np.ctypeslib.as_array(data_p, shape=(n, c)).copy()
        labels = None
        if labels_p:
            labels = np.ctypeslib.as_array(labels_p, shape=(n,)).copy()
    finally:
        lib.tpugbdt_free(data_p)
        if labels_p:
            lib.tpugbdt_free(labels_p)
    return mat, labels, fmt.value

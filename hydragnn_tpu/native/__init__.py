"""ctypes bindings for the native runtime (native/hydrastore.cpp).

The shared library is compiled on demand with g++ and cached next to the
source.  The cache is keyed on a HASH of the source stored beside the
library, not on mtimes: a copied tree (the chip tool's, a CI checkout)
does not preserve them, and must load what the tracked source builds and
nothing older.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

from hydragnn_tpu.utils.runtime import checkout_root

_REPO_ROOT = checkout_root()
_SRC = os.path.join(_REPO_ROOT, "native", "hydrastore.cpp")
_LIB = os.path.join(_REPO_ROOT, "native", "libhydrastore.so")
_STAMP = _LIB + ".srchash"

_lib: Optional[ctypes.CDLL] = None


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stale() -> bool:
    """True unless the library exists AND its stamp names this source."""
    if not os.path.exists(_LIB):
        return True
    try:
        with open(_STAMP) as f:
            return f.read().strip() != _src_hash()
    except FileNotFoundError:
        return True


def _build() -> None:
    # build aside and rename into place: concurrent processes (test
    # workers, fleet children) never load a half-written library
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-pthread", "-std=c++17",
           _SRC, "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, _LIB)
    with open(tmp, "w") as f:
        f.write(_src_hash() + "\n")
    os.replace(tmp, _STAMP)


def load_library() -> ctypes.CDLL:
    """Load (building if stale) the native library."""
    global _lib
    if _lib is not None:
        return _lib
    if _stale():
        _build()
    lib = ctypes.CDLL(_LIB)

    # gpack
    lib.gpack_open.restype = ctypes.c_void_p
    lib.gpack_open.argtypes = [ctypes.c_char_p]
    lib.gpack_close.argtypes = [ctypes.c_void_p]
    lib.gpack_num_samples.restype = ctypes.c_uint64
    lib.gpack_num_samples.argtypes = [ctypes.c_void_p]
    lib.gpack_num_keys.restype = ctypes.c_uint64
    lib.gpack_num_keys.argtypes = [ctypes.c_void_p]
    lib.gpack_key_name.restype = ctypes.c_char_p
    lib.gpack_key_name.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.gpack_key_dtype.restype = ctypes.c_uint32
    lib.gpack_key_dtype.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.gpack_key_ndim.restype = ctypes.c_uint32
    lib.gpack_key_ndim.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.gpack_attrs_json.restype = ctypes.c_char_p
    lib.gpack_attrs_json.argtypes = [ctypes.c_void_p]
    lib.gpack_sample_dims.restype = ctypes.c_int64
    lib.gpack_sample_dims.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.gpack_sample_ptr.restype = ctypes.c_void_p
    lib.gpack_sample_ptr.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]

    # dstore
    lib.dstore_create.restype = ctypes.c_void_p
    lib.dstore_create.argtypes = [ctypes.c_int]
    lib.dstore_port.restype = ctypes.c_int
    lib.dstore_port.argtypes = [ctypes.c_void_p]
    lib.dstore_add.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64]
    lib.dstore_get_local.restype = ctypes.c_int64
    lib.dstore_get_local.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_int64]
    lib.dstore_connect.restype = ctypes.c_int
    lib.dstore_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.dstore_connect_timeout.restype = ctypes.c_int
    lib.dstore_connect_timeout.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.dstore_fetch.restype = ctypes.c_int64
    lib.dstore_fetch.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_int64]
    lib.dstore_disconnect.argtypes = [ctypes.c_int]
    lib.dstore_destroy.argtypes = [ctypes.c_void_p]

    _lib = lib
    return lib


def available() -> bool:
    try:
        load_library()
        return True
    except Exception:  # graftlint: disable=ROB001 (capability probe; False IS the answer)
        return False

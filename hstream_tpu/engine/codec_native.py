"""ctypes binding for the native wire-encode kernels and the string
key table (cpp/encode.cpp).

Build-on-demand like the native store (store/build.py); `load()` returns
None when no toolchain is available: the transport falls back to its
pure-numpy packer, the key table (engine/keytable.py) to a dict.
"""

from __future__ import annotations

import ctypes as C
import os
import threading

from hstream_tpu.common.nativebuild import build_so

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "cpp", "encode.cpp")
SO = os.path.join(_DIR, "cpp", "libencode.so")

_lock = threading.Lock()
_lib: C.CDLL | None = None
_tried = False

_i64 = C.c_int64
_p_i64 = C.POINTER(C.c_int64)
_p_i32 = C.POINTER(C.c_int32)
_p_u32 = C.POINTER(C.c_uint32)
_p_u8 = C.POINTER(C.c_uint8)
_p_f32 = C.POINTER(C.c_float)


def build(force: bool = False) -> str:
    """Compile cpp/encode.cpp -> cpp/libencode.so if stale (or always,
    with `force`); returns the .so path. Raises when it cannot."""
    return build_so(SRC, SO, opt="-O3", force=force)


def load() -> C.CDLL | None:
    """The native codec library, built on first use; None if unbuildable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            lib = C.CDLL(build())
        except Exception:
            return None
        lib.enc_pack_i64.argtypes = [_p_i64, _i64, _i64, C.c_int,
                                     _p_u32, _i64]
        lib.enc_pack_i32.argtypes = [_p_i32, _i64, _i64, C.c_int,
                                     _p_u32, _i64]
        lib.enc_pack_diff_i64.argtypes = [_p_i64, _i64, C.c_int,
                                          _p_u32, _i64]
        lib.enc_pack_bool.argtypes = [_p_u8, _i64, _p_u32, _i64]
        lib.enc_minmax_i64.argtypes = [_p_i64, _i64, _p_i64, _p_i64]
        lib.enc_minmax_i32.argtypes = [_p_i32, _i64, _p_i64, _p_i64]
        lib.enc_diff_stats_i64.argtypes = [_p_i64, _i64, _p_i64]
        lib.enc_diff_stats_i64.restype = C.c_int32
        lib.enc_quantize_f32.argtypes = [_p_f32, _i64, C.c_float,
                                         C.c_float, _i64, _p_i32,
                                         _p_i64, _p_i64]
        lib.enc_quantize_f32.restype = C.c_int32
        # string -> key id table (engine/keytable.py)
        lib.kt_new.argtypes = []
        lib.kt_new.restype = C.c_void_p
        lib.kt_free.argtypes = [C.c_void_p]
        lib.kt_free.restype = None
        lib.kt_size.argtypes = [C.c_void_p]
        lib.kt_size.restype = _i64
        lib.kt_resolve.argtypes = [C.c_void_p, C.c_char_p, _i64, _i64,
                                   _p_i32]
        lib.kt_resolve.restype = _i64
        lib.kt_insert.argtypes = [C.c_void_p, C.c_char_p, _i64, _i64,
                                  _p_i32]
        lib.kt_insert.restype = _i64
        _lib = lib
        return _lib

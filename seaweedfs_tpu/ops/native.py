"""ctypes bindings for the native C++ library (native/ec_native.cpp).

Builds the shared libraries on first use (make in native/); callers must
tolerate `lib() is None` when no toolchain is available — a failed build
is logged once, with the compiler's stderr.
"""

from __future__ import annotations

import ctypes
import functools
import os
import subprocess

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libseaweedec.so")


@functools.lru_cache(maxsize=1)
def build() -> bool:
    """Run `make` in native/ once per process (both libraries; shared
    with storage/native_engine.py).  It is a no-op when the .so files
    are fresh and rebuilds after source edits.  A failure is logged here,
    once, with its stderr; callers then use a prebuilt library if one
    exists and their host fallbacks otherwise."""
    from ..util import glog

    try:
        subprocess.run(["make", "-s"], cwd=_NATIVE_DIR, check=True,
                       capture_output=True, timeout=300)
        return True
    except subprocess.CalledProcessError as e:
        glog.errorf("native build failed (make exit %d) in %s:\n%s",
                    e.returncode, _NATIVE_DIR,
                    e.stderr.decode(errors="replace")[-4000:])
    except (OSError, subprocess.TimeoutExpired) as e:
        glog.errorf("native build did not run in %s: %s: %s",
                    _NATIVE_DIR, type(e).__name__, e)
    return False


@functools.lru_cache(maxsize=1)
def lib() -> ctypes.CDLL | None:
    build()
    try:
        cdll = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    cdll.sw_crc32c.restype = ctypes.c_uint32
    cdll.sw_crc32c.argtypes = [
        ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t,
    ]
    cdll.sw_gf_apply_matrix.restype = None
    cdll.sw_gf_apply_matrix.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    cdll.sw_has_avx2.restype = ctypes.c_int
    cdll.sw_has_avx2.argtypes = []
    cdll.sw_cpu_level.restype = ctypes.c_int
    cdll.sw_cpu_level.argtypes = []
    cdll.sw_gf_apply_matrix_force.restype = None
    cdll.sw_gf_apply_matrix_force.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_int,
    ]
    cdll.sw_encode_rows.restype = None
    cdll.sw_encode_rows.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    if hasattr(cdll, "sw_inline_scatter"):  # absent in stale prebuilt libs
        cdll.sw_inline_scatter.restype = ctypes.c_int
        cdll.sw_inline_scatter.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_uint64,
        ]
    return cdll


def has_avx2() -> bool:
    cdll = lib()
    return bool(cdll and cdll.sw_has_avx2())


def cpu_level() -> int:
    """Best GF kernel level: 0 scalar, 1 AVX2-PSHUFB, 2 GFNI+AVX2,
    3 GFNI+AVX-512 (see native/ec_native.cpp kernel ladder)."""
    cdll = lib()
    return int(cdll.sw_cpu_level()) if cdll else 0

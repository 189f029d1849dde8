"""Backend-selectable Reed-Solomon codec — the `reedsolomon.Encoder` seam.

The reference's storage engine calls exactly three codec methods
(Encode / Reconstruct / ReconstructData; SURVEY.md §2) behind
`reedsolomon.New(10, 4)`.  `new_encoder(...)` is the equivalent factory,
selected by backend the way the north-star design selects `-ec.backend=tpu`:

  * "tpu"   — JAX kernels (Pallas MXU on TPU, SWAR on CPU), rs_jax.py
  * "cpu"   — native AVX2 C++ (klauspost-equivalent), this module
  * "numpy" — pure NumPy reference, rs_numpy.py
  * "auto"  — tpu when a TPU is attached, else cpu-native, else numpy
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from . import native
from ..util import glog
from ..util.platform import jax_usable, on_tpu
from .rs_numpy import (NumpyEncoder, ReconstructError,  # noqa: F401
                       RSCodecBase, decode_plan_cache_info, decode_rows,
                       gf_apply_matrix)


class NativeEncoder(RSCodecBase):
    """CPU codec backed by the C++ kernel ladder in native/ec_native.cpp
    (GFNI+AVX-512 > GFNI+AVX2 > AVX2-PSHUFB > scalar, runtime-dispatched)."""

    def __init__(self, data_shards: int = 10, parity_shards: int = 4):
        super().__init__(data_shards, parity_shards)
        self._lib = native.lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")

    def _apply(self, matrix: np.ndarray, inputs: np.ndarray) -> np.ndarray:
        p, d = matrix.shape
        length = inputs.shape[1]
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        inputs = np.ascontiguousarray(inputs, dtype=np.uint8)
        out = np.zeros((p, length), dtype=np.uint8)
        self._lib.sw_gf_apply_matrix(
            matrix.ctypes.data_as(ctypes.c_char_p), p, d,
            inputs.ctypes.data_as(ctypes.c_char_p), length,
            out.ctypes.data_as(ctypes.c_char_p))
        return out

    def encode_rows(self, parity_matrix: np.ndarray, data: np.ndarray,
                    parity_out: np.ndarray) -> list[int]:
        """Fused span encode: data (R, d, L) -> parity_out (R, p, L), one
        ctypes call; returns per-shard CRC32Cs chained across the R rows
        (= the rolling file CRC of the span's L*R-byte shard slice).

        Buffer ownership contract: the CALLER owns both buffers, and the
        kernel only touches them for the duration of this call — `data`
        is read-only, `parity_out` is fully overwritten before return.
        Nothing is retained, so a write-behind pipeline may hand either
        buffer to another thread (or recycle it through a slot pool) the
        moment this returns; conversely neither buffer may be mutated BY
        other threads while the call is in flight.  All three arrays
        must be C-contiguous uint8 — the kernel walks raw pointers with
        row strides computed from the shapes."""
        for name, arr in (("parity_matrix", parity_matrix),
                          ("data", data), ("parity_out", parity_out)):
            if arr.dtype != np.uint8 or not arr.flags["C_CONTIGUOUS"]:
                raise ValueError(
                    f"encode_rows: {name} must be C-contiguous uint8 "
                    f"(got dtype={arr.dtype}, "
                    f"contiguous={arr.flags['C_CONTIGUOUS']})")
        p, d = parity_matrix.shape
        rows, _, length = data.shape
        if parity_out.shape != (rows, p, length):
            raise ValueError(
                f"encode_rows: parity_out shape {parity_out.shape} != "
                f"{(rows, p, length)}")
        crcs = (ctypes.c_uint32 * (d + p))()
        self._lib.sw_encode_rows(
            parity_matrix.ctypes.data_as(ctypes.c_char_p), p, d,
            data.ctypes.data_as(ctypes.c_char_p), length, rows,
            parity_out.ctypes.data_as(ctypes.c_char_p), crcs,
        )
        return list(crcs)


# Spans below this stay on the host codec: a device dispatch + two link
# round-trips cost more than the mat-vec itself for small recoveries.
_RECOVER_DEVICE_MIN_BYTES = int(
    os.environ.get("WEED_EC_RECOVER_DEVICE_MIN_KB", "512") or 0) << 10


def recover_device_min_bytes() -> int:
    """WEED_EC_RECOVER_DEVICE_MIN_KB re-read per call (daemons and tests
    flip it without reimporting); import-time value is the fallback."""
    kb = os.environ.get("WEED_EC_RECOVER_DEVICE_MIN_KB", "")
    if not kb:
        return _RECOVER_DEVICE_MIN_BYTES
    try:
        return int(kb) << 10
    except ValueError:
        return _RECOVER_DEVICE_MIN_BYTES


def recover_device_enabled() -> bool:
    """Whether reconstruct_span may dispatch to a device kernel.
    WEED_EC_RECOVER_DEVICE: unset/"auto" -> only on a real TPU; "1"
    forces it on (any jax backend — the CPU mesh harness and tests);
    "0" disables.  Both answers come from this process's own backend,
    so a forked prefork worker never dispatches."""
    v = os.environ.get("WEED_EC_RECOVER_DEVICE", "auto").lower()
    if v in ("1", "true", "yes", "force"):
        return jax_usable()
    if v in ("0", "false", "no"):
        return False
    return on_tpu()


_device_counts_lock = threading.Lock()
_device_counts = {"device_decodes": 0, "device_fallbacks": 0}


def _count_device(ok: bool):
    from ..stats import metrics as stats

    with _device_counts_lock:
        _device_counts["device_decodes" if ok else "device_fallbacks"] += 1
    stats.EcRecoverDeviceCounter.labels("ok" if ok else "fallback").inc()


def recover_device_counts() -> dict:
    """Process-wide {"device_decodes", "device_fallbacks"}: decodes
    reconstruct_span served on the device, and decodes whose device
    dispatch failed and were re-run on the host."""
    with _device_counts_lock:
        return dict(_device_counts)


def _apply_rows_host(rows: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """(t, d) decode rows x (d, L) survivor spans on the best host
    backend: the native kernel ladder when built, else NumPy tables."""
    lib = native.lib()
    if lib is None:
        return gf_apply_matrix(rows, inputs)
    t, d = rows.shape
    length = inputs.shape[1]
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    inputs = np.ascontiguousarray(inputs, dtype=np.uint8)
    out = np.zeros((t, length), dtype=np.uint8)
    lib.sw_gf_apply_matrix(
        rows.ctypes.data_as(ctypes.c_char_p), t, d,
        inputs.ctypes.data_as(ctypes.c_char_p), length,
        out.ctypes.data_as(ctypes.c_char_p))
    return out


def _discard_stage(stage: str, seconds: float):
    """reconstruct_span's stage accumulator when the caller keeps none."""


def _apply_rows_device(rows, to_dev: np.ndarray, out_rows: int, fam_name,
                       survivors, slab_key, add_stage) -> np.ndarray:
    """(t, d) decode rows x (d, L) survivor spans on the default JAX
    device: the Pallas kernel on a TPU, the SWAR XLA apply elsewhere.
    Two stages for `add_stage`: decode_h2d (the upload; a resident-slab
    hit records nothing) and decode_apply (apply_matrix called ->
    np.asarray returned: dispatch, kernel, copy back)."""
    import jax
    import jax.numpy as jnp

    from .. import tracing
    from .device_pool import get_pool
    from .rs_jax import apply_matrix

    dev0 = jax.devices()[0]
    method = "pallas" if dev0.platform == "tpu" else "swar"
    if slab_key is None:
        with tracing.stage("ec.recover.decode.apply", add_stage,
                           "decode_apply", -1, to_dev.nbytes):
            return np.asarray(apply_matrix(
                np.asarray(rows), to_dev, method=method))[:out_rows]
    pool = get_pool()
    # survivor slabs upload to the default device; labeling the
    # transfers/residency keeps the recover traffic distinguishable
    # from the sharded encode meshes'
    dev_label = str(dev0)
    key = ("recover", fam_name, tuple(survivors), slab_key)

    def _upload():
        with tracing.stage("ec.recover.decode.h2d", add_stage,
                           "decode_h2d", -1, to_dev.nbytes):
            dev = jnp.asarray(to_dev)
        pool.note_h2d(to_dev.nbytes, device=dev_label)
        return dev

    dev_in = pool.acquire_resident(key, _upload, to_dev.nbytes)
    try:
        with tracing.stage("ec.recover.decode.apply", add_stage,
                           "decode_apply", -1, to_dev.nbytes):
            out = np.asarray(apply_matrix(
                np.asarray(rows), dev_in, method=method))[:out_rows]
    finally:
        pool.release_resident(key)
    pool.note_d2h(out.nbytes, device=dev_label)
    return out


def reconstruct_span(survivors, inputs: np.ndarray, target: int,
                     data_shards: int = 10,
                     total_shards: int = 14,
                     slab_key=None, family=None,
                     add_stage=_discard_stage) -> np.ndarray:
    """Target-row reconstruction: rebuild ONE shard's span from the
    (d, L) survivor stack via the cached decode plan — one GF mat-vec,
    never a full Reconstruct.  `inputs[i]` must be the span read from
    `survivors[i]`.  L may be many coalesced spans laid end to end (the
    batched multi-span decode): the math is column-wise, so stacking is
    free.  Dispatch: fused JAX/Pallas kernel for large spans on a TPU,
    native/NumPy host kernel for small ones.

    slab_key: any hashable that stands for the content of `inputs`: the
    caller's name for these bytes (a mounted sealed volume's token and
    the spans' offsets and lengths: ec_volume.py), never a pass over
    them.  Two stacks of different bytes must never share one.  When
    set, the device upload routes through the EC device slab pool
    (ops/device_pool.py) keyed by (survivors, slab_key): consecutive
    decodes against the same survivor spans — a different missing
    target, or a block re-recovered after LRU eviction — hit the
    HBM-resident slab instead of re-uploading over the link.

    family: an erasure_coding.codes CodeFamily.  None (or the RS default)
    keeps the classic (total, data) path; other families supply their own
    cached decode plan (each family's cheap inversion), and vector codes
    (sub_shards > 1) run the same kernels over the lane-interleaved view
    of the survivor stack.

    add_stage: `(stage, seconds)` accumulator of the device path's two
    stages (RecoverStats.add_stage on the degraded-read path)."""
    fam_name = getattr(family, "name", None)
    if family is not None and fam_name != "rs_vandermonde":
        rows = family.decode_rows(tuple(survivors), (target,))
        to_dev = family.to_lanes(np.ascontiguousarray(inputs))
        out_rows = len(rows)
    else:
        family = None
        rows = decode_rows(data_shards, total_shards, survivors, (target,))
        to_dev = inputs
        out_rows = 1

    def _finish(out: np.ndarray) -> np.ndarray:
        return out[0] if family is None else family.from_lanes(out)[0]

    if inputs.nbytes >= recover_device_min_bytes() \
            and recover_device_enabled():
        try:
            out = _apply_rows_device(rows, to_dev, out_rows, fam_name,
                                     survivors, slab_key, add_stage)
        except Exception as e:
            # mid-incident the read must still be served, and the host
            # path always works — but a device failure is never silent
            _count_device(ok=False)
            glog.errorf("ec recover: device decode failed (%s: %s); "
                        "served by the host codec", type(e).__name__, e)
        else:
            _count_device(ok=True)
            return _finish(out)
    return _finish(_apply_rows_host(rows, to_dev)[:out_rows])


def new_host_encoder(data_shards: int = 10, parity_shards: int = 4):
    """Best HOST codec (native AVX2/SSE, else numpy) — never a device
    backend.  The link-throughput auto-selection falls back to this when
    the host<->device link would cap the device path below the host
    rate; resolving "auto" there would pick the device codec again."""
    if native.lib() is not None:
        return NativeEncoder(data_shards, parity_shards)
    return NumpyEncoder(data_shards, parity_shards)


def new_encoder(data_shards: int = 10, parity_shards: int = 4,
                backend: str = "auto"):
    if backend == "auto":
        if on_tpu():
            backend = "tpu"
        elif native.lib() is not None:
            backend = "cpu"
        else:
            backend = "numpy"
    if backend in ("tpu", "jax"):
        from .rs_jax import JaxEncoder

        method = "pallas" if backend == "tpu" and on_tpu() else "swar"
        return JaxEncoder(data_shards, parity_shards, method=method)
    if backend == "cpu":
        return NativeEncoder(data_shards, parity_shards)
    if backend == "numpy":
        return NumpyEncoder(data_shards, parity_shards)
    raise ValueError(f"unknown backend {backend!r}")

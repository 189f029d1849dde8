"""Pallas TPU kernel for GF(2^8) matrix application (RS encode/reconstruct).

Strategy: the GF(2) bit-matmul formulation (see rs_jax.py docstring) with the
bit-slice -> MXU matmul -> bit-pack pipeline fused inside one kernel, so the
8x-expanded bit-sliced intermediate lives only in VMEM and HBM traffic stays
at (d + p) * L bytes.  The grid walks the byte axis; each program handles a
(d, BLOCK) tile of packed bytes.

Replaces klauspost enc.Encode's SIMD inner loop
(/root/reference/weed/storage/erasure_coding/ec_encoder.go:198) with an MXU
systolic-array contraction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gf256

DEFAULT_BLOCK = 8192


def _gf_apply_kernel(bm_ref, x_ref, o_ref, *, d: int, p: int):
    x = x_ref[:].astype(jnp.int32)  # (d, BLOCK) bytes as int32
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = ((x[:, None, :] >> shifts) & 1).astype(jnp.int8)
    bits = bits.reshape(d * 8, x.shape[-1])
    # XOR == add mod 2: integer matmul on the MXU, then take the low bit.
    prod = jax.lax.dot(
        bm_ref[:], bits, preferred_element_type=jnp.int32
    )  # (p*8, BLOCK)
    out_bits = (prod & 1).reshape(p, 8, x.shape[-1])
    weights = jnp.left_shift(1, shifts)  # (1, 8, 1)
    o_ref[:] = (out_bits * weights).sum(axis=1).astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("out_rows", "block", "interpret")
)
def _apply_pallas(bit_matrix, data, out_rows: int, block: int,
                  interpret: bool):
    d, length = data.shape
    grid = (pl.cdiv(length, block),)
    kernel = functools.partial(_gf_apply_kernel, d=d, p=out_rows)
    # the scope is the kernel's name in a trace: it outlives a rename of
    # this function (whose Python name a benchmark pattern also matches)
    with jax.named_scope("ec.recover.apply"):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((out_rows, length), jnp.uint8),
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (out_rows * 8, d * 8),
                    lambda i: (0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (d, block), lambda i: (0, i), memory_space=pltpu.VMEM
                ),
            ],
            out_specs=pl.BlockSpec(
                (out_rows, block), lambda i: (0, i),
                memory_space=pltpu.VMEM
            ),
            interpret=interpret,
            cost_estimate=pl.CostEstimate(
                flops=2 * out_rows * 8 * d * 8 * length,
                bytes_accessed=(d + out_rows) * length,
                transcendentals=0,
            ),
        )(bit_matrix, data)


def _interpret_for(x) -> bool:
    """Mosaic compiles for a TPU only; everywhere else the kernel runs
    in Pallas interpret mode.  Decided by where `x` actually lives (a
    tracer has no placement yet: the default backend will run it)."""
    if isinstance(x, jax.core.Tracer):
        return jax.default_backend() != "tpu"
    return next(iter(x.devices())).platform != "tpu"


def apply_matrix_pallas(matrix: np.ndarray, data, block: int = DEFAULT_BLOCK,
                        interpret: bool | None = None):
    """out[i] = XOR_j gf_mul(matrix[i,j], data[j]).  data: (d, L) uint8."""
    from .rs_jax import _bit_matrix_cached, _matrix_key

    p, d = matrix.shape
    bm = jnp.asarray(_bit_matrix_cached(*_matrix_key(matrix)))
    data = jnp.asarray(data, dtype=jnp.uint8)
    if interpret is None:
        interpret = _interpret_for(data)
    return _apply_pallas(bm, data, p, block, interpret)


# ---------------------------------------------------------------------------
# Fused batched parity + CRC32C kernel (the production encode step).
#
# The XLA formulation (parallel/mesh.batched_encode_step) materializes the
# 8x bit expansion in HBM twice (parity matmul input + CRC matmul input).
# Here one VMEM-resident expansion feeds both, and the data rides the MXU
# in WORD layout: 4 packed bytes per int32 lane.  That makes the bit
# expansion rows (shard, byteidx, plane) = d*32 rows per W = BLOCK/4
# lanes, so
#
#   * the parity matmul is (p*32, d*32) @ (d*32, W) — a full 128-row MXU
#     tile at 4x fewer lane tiles than the byte layout, and
#   * the CRC matmul is (d*32, W) @ (W, 32) — W/128 weight tiles against
#     the plane-7 segment matrix restricted to word-anchor byte positions.
#
# Byte-position and bit-plane dependence of CRC32C folds into per-
# (byteidx, plane) 32x32 GF(2) advance corrections applied OUTSIDE the
# kernel on the tiny (B, nseg, 14*32)-word partials:
#
#   true[(s, bi, b)] = Bz^(7-b-8*bi) @ raw[(s, bi, b)]
#
# with Bz the one-zero-BIT CRC advance (all powers commute; verified
# against the byte-layout segment matrices).  Segments then combine into
# whole-chunk CRCs with the log-tree of 32x32 advance matrices from
# ops/crc_device.py.
#
# Parity stays in packed int32 words end-to-end: a device-side
# int32->uint8 bitcast is a byte-granular relayout on TPU (measured 10x
# the kernel's own cost), while host-side numpy views of the downloaded
# words are free.  Measured on TPU v5e at the shipped 32 KiB fused
# block: ~60 GiB/s fused vs ~60 GiB/s parity-only — CRC fusion is
# essentially free (the round-3 plane-partial byte-layout kernel ran
# 26 GiB/s; see DEFAULT_FUSED_BLOCK below for the block sweep).
# ---------------------------------------------------------------------------

_POLY_REFLECTED = 0x82F63B78


def _gf2_inv(m: np.ndarray) -> np.ndarray:
    """Inverse of a GF(2) matrix via Gaussian elimination."""
    n = m.shape[0]
    a = m.astype(np.uint8).copy()
    inv = np.eye(n, dtype=np.uint8)
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r, col])
        if piv != col:
            a[[col, piv]] = a[[piv, col]]
            inv[[col, piv]] = inv[[piv, col]]
        for r in range(n):
            if r != col and a[r, col]:
                a[r] ^= a[col]
                inv[r] ^= inv[col]
    return inv


@functools.lru_cache(maxsize=1)
def _bit_advance() -> np.ndarray:
    """Bz: 32x32 GF(2) one-zero-BIT advance of the raw CRC32C state
    (s' = (s >> 1) ^ (POLY if s & 1)); Bz^8 equals the one-byte advance
    crc32c._advance_one()."""
    from . import crc32c as crc_host

    def col(i):
        s = 1 << i
        return crc_host._bits_of((s >> 1)
                                 ^ (_POLY_REFLECTED if s & 1 else 0))
    return np.stack([col(i) for i in range(32)], axis=1).astype(np.uint8)


@functools.lru_cache(maxsize=1)
def _word_corrections() -> np.ndarray:
    """CT (4, 8, 32, 32) int8: CT[bi, b] = (Bz^(7-b) Bz^(-8 bi))^T, the
    row-transform turning a raw word-anchor partial into the true
    (byteidx bi, plane b) contribution."""
    bz = _bit_advance().astype(np.int64)
    bzinv = _gf2_inv(_bit_advance()).astype(np.int64)
    out = np.zeros((4, 8, 32, 32), dtype=np.int8)
    for bi in range(4):
        for b in range(8):
            m = (np.linalg.matrix_power(bz, 7 - b)
                 @ np.linalg.matrix_power(bzinv, 8 * bi)) % 2
            out[bi, b] = m.T.astype(np.int8)
    return out


@functools.lru_cache(maxsize=8)
def _anchor_matrix(block: int) -> np.ndarray:
    """V (block//4, 32) int8: plane-7 segment-CRC images at the word
    anchor byte positions 4w of a block-byte segment."""
    from .crc_device import _segment_matrix

    w = _segment_matrix(block)  # (8*block, 32) plane-major rows
    return np.ascontiguousarray(w.reshape(8, block, 32)[7][::4])


@functools.lru_cache(maxsize=4)
def _bm_word_cached(matrix_bytes: bytes, p: int, d: int) -> np.ndarray:
    """The (p*32, d*32) word-layout GF(2) bit matrix: block-diagonal over
    byteidx (RS parity is per-byte, so word bit k=8*bi+b maps within its
    own byte group)."""
    from .rs_jax import _bit_matrix_cached

    bm = _bit_matrix_cached(matrix_bytes, p, d)
    bmr = bm.reshape(p, 8, d, 8)
    bmw = np.zeros((p, 4, 8, d, 4, 8), np.int8)
    for bi in range(4):
        bmw[:, bi, :, :, bi, :] = bmr
    return np.ascontiguousarray(bmw.reshape(p * 32, d * 32))


def _fused_words_kernel(bmw_ref, v_ref, x_ref, par_ref, crc_ref, *,
                        d: int, p: int):
    xw = x_ref[0]  # (d, W) int32 packed little-endian bytes
    w = xw.shape[-1]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    bits = ((xw[:, None, :] >> shifts) & 1).astype(jnp.int8)
    bits = bits.reshape(d * 32, w)  # rows (shard, byteidx, plane)
    prod = jax.lax.dot(bmw_ref[:], bits,
                       preferred_element_type=jnp.int32)  # (p*32, W)
    out_bits = prod & 1
    # pack parity bit rows back into int32 words (wrapping shifts leave
    # exactly the right bit pattern)
    wts = jnp.left_shift(jnp.int32(1), shifts)
    par_ref[0] = (out_bits.reshape(p, 32, w) * wts).sum(axis=1)
    # raw CRC partials: one narrow matmul against the anchor matrix; the
    # parity shards' partials follow algebraically through the same bit
    # matrix (parity bits are GF(2)-linear in data bits per position)
    yd = jax.lax.dot(bits, v_ref[:], preferred_element_type=jnp.int32)
    yd8 = (yd & 1).astype(jnp.int8)  # (d*32, 32)
    yp = jax.lax.dot(bmw_ref[:], yd8,
                     preferred_element_type=jnp.int32)  # (p*32, 32)
    y_all = jnp.concatenate([yd8.astype(jnp.int32), yp & 1], axis=0)
    # pack each row's 32 bits into an int32 word (Mosaic has no unsigned
    # reductions; bit 31 rides the sign bit with the right pattern)
    w32 = jnp.left_shift(
        jnp.int32(1), jax.lax.broadcasted_iota(jnp.int32, (1, 32), 1))
    packed = (y_all * w32).sum(axis=-1)  # ((d+p)*32,) int32
    # output tiles need (8, 128)-aligned trailing dims: (d+p)*32 = 448
    # raw words ride row 0 of an (8, 512) tile
    tile = jnp.pad(packed[None, :], ((0, 7), (0, 512 - (d + p) * 32)))
    crc_ref[0, 0] = jax.lax.bitcast_convert_type(tile, jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("d", "p", "block", "interpret"))
def _fused_encode_words(bmw, v, words, d: int, p: int, block: int,
                        interpret: bool):
    b, _, lw = words.shape
    wblk = block // 4
    nseg = (lw * 4) // block
    kernel = functools.partial(_fused_words_kernel, d=d, p=p)
    with jax.named_scope("ec.encode.fused_words"):
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((b, p, lw), jnp.int32),
                jax.ShapeDtypeStruct((b, nseg, 8, 512), jnp.uint32),
            ),
            grid=(b, nseg),
            in_specs=[
                pl.BlockSpec((p * 32, d * 32), lambda bi, i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((wblk, 32), lambda bi, i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, d, wblk), lambda bi, i: (bi, 0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, p, wblk), lambda bi, i: (bi, 0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, 8, 512), lambda bi, i: (bi, i, 0, 0),
                             memory_space=pltpu.VMEM),
            ),
            interpret=interpret,
            cost_estimate=pl.CostEstimate(
                flops=2 * (p * 32 * d * 32 + d * 32 * 32) * lw * b,
                bytes_accessed=(d + p) * lw * 4 * b,
                transcendentals=0,
            ),
        )(bmw, v, words)


# The fused words kernel runs FASTER at larger in-kernel segments
# (fewer grid steps, better MXU amortisation): measured on TPU v5e at
# (6, 10, 1 MiB): 8192 -> 49.5, 16384 -> 58.2, 32768 -> 59.9 GiB/s
# (parity-only ceiling 60.2 — CRC fusion is essentially free at 32 KiB).
DEFAULT_FUSED_BLOCK = 32768


def fused_encode_block(length: int,
                       block: int = DEFAULT_FUSED_BLOCK) -> int:
    """Largest kernel block that divides length with a power-of-two
    segment count, or 0 when the fused kernel cannot handle this shape."""
    while block >= 512:
        nseg = length // block
        if length % block == 0 and nseg > 0 and nseg & (nseg - 1) == 0:
            return block
        block //= 2
    return 0


def fused_encode_words(matrix: np.ndarray, words,
                       block: int | None = None,
                       interpret: bool | None = None):
    """Batched parity + per-shard raw CRC32C, word-layout (the production
    encode step).

    words: (B, d, L//4) int32 — each lane is 4 consecutive shard bytes,
    little-endian (a free numpy .view(np.int32) of the (B, d, L) uint8
    host buffer).  Returns (parity_words (B, p, L//4) int32, crc_raw
    (B, d+p) uint32).  Parity words are the packed parity bytes — view
    the downloaded array as uint8 on the host; no device bitcast happens
    in either direction.  L must divide into a power-of-two count of
    `block`-byte segments (check with fused_encode_block first)."""
    from .crc_device import combine_tree
    from .rs_jax import _matrix_key

    p, d = matrix.shape
    words = jnp.asarray(words, dtype=jnp.int32)
    length = words.shape[-1] * 4
    if block is None:
        block = fused_encode_block(length)
    if not block or block % 4:
        raise ValueError(f"length {length} unsupported by fused kernel")
    nseg = length // block
    bmw = jnp.asarray(_bm_word_cached(*_matrix_key(matrix)))
    v = jnp.asarray(_anchor_matrix(block))
    if interpret is None:
        interpret = _interpret_for(words)
    parity_w, tiles = _fused_encode_words(bmw, v, words, d, p, block,
                                          interpret)
    # per-(byteidx, plane) advance corrections + the shared combine fold:
    # tiny (B * nseg * 448 words) XLA work next to the kernel itself
    with jax.named_scope("ec.crc32c"):
        packed = tiles[:, :, 0, :(d + p) * 32]
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = ((packed[..., None] >> shifts) & 1).astype(jnp.int8)
        bits = bits.reshape(*packed.shape[:2], d + p, 4, 8, 32)
        ct = jnp.asarray(_word_corrections())
        corr = jnp.einsum("bnsiqc,iqcd->bnsd", bits, ct,
                          preferred_element_type=jnp.int32) & 1
        state = corr.astype(jnp.int8).transpose(0, 2, 1, 3)
        return parity_w, combine_tree(state, block, nseg)


def fused_encode_pallas(matrix: np.ndarray, data,
                        block: int | None = None,
                        interpret: bool | None = None):
    """Byte-layout convenience wrapper over fused_encode_words.

    data: (B, d, L) uint8 -> (parity (B, p, L) uint8, crc_raw (B, d+p)
    uint32), same contract as parallel.mesh.batched_encode_step.  The
    device-side uint8<->int32 bitcasts this needs are relayouts on TPU —
    production paths (parallel/batched_encode.py) upload int32 views and
    call fused_encode_words directly."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    b, d, length = data.shape
    words = jax.lax.bitcast_convert_type(
        data.reshape(b, d, length // 4, 4), jnp.int32)
    parity_w, crc_raw = fused_encode_words(matrix, words, block=block,
                                           interpret=interpret)
    parity = jax.lax.bitcast_convert_type(
        parity_w, jnp.uint8).reshape(b, matrix.shape[0], length)
    return parity, crc_raw

"""Device-fused CRC32C: batched checksums as GF(2) bit-matmuls.

The reference computes needle CRC32C on the CPU at write time only
(/root/reference/weed/storage/needle/crc.go:12-33).  The TPU build fuses
integrity checksums into the batched encode pass (BASELINE config 5): while
a (B, S, L) block batch is HBM-resident for parity generation, per-chunk
CRCs ride the same MXU machinery.

Formulation — CRC32C's state update is jointly GF(2)-linear in
(state, byte), so for a chunk M the "raw" image g(M) = raw_update(0, M)
decomposes:

  1. split M into 2^k segments; per-segment g = bit-matmul of the segment's
     bits with a precomputed (8*seg, 32) GF(2) matrix W, where
     W[8j+b] = Adv_{seg-1-j}(T[1<<b]) — one MXU dot per batch;
  2. combine adjacent segments with a log-tree of 32x32 advance-matrix
     multiplies: g(A||B) = Adv_{|B|}(g(A)) ^ g(B);
  3. host finalizes: crc32c(M) = g(M) ^ crc32c_zeros(len(M))
     (ops/crc32c.finalize_raw).

Front zero-padding leaves g unchanged (state 0 is a fixed point of zero
bytes), so chunks pad to 2^k * seg for free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import crc32c as crc_host


def _plan_segments(length: int) -> tuple[int, int]:
    """(nseg, seg) with nseg a power of two and nseg * seg >= length.

    Targets kiB-scale segments (deep contraction dim for the MXU) with at
    most 2^8 segments (shallow combine tree, small compiled graph).
    """
    if length <= 0:
        raise ValueError(f"chunk length must be positive, got {length}")
    nseg = 1
    while nseg < 256 and (length + nseg - 1) // nseg > 1024:
        nseg *= 2
    seg = (length + nseg - 1) // nseg
    return nseg, seg


@functools.lru_cache(maxsize=32)
def _segment_matrix(seg: int) -> np.ndarray:
    """W (8*seg, 32) int8 in bit-PLANE-major row order (row b*seg + j =
    bits of g(byte (1<<b) at offset j of a seg-byte segment) =
    Adv_{seg-1-j} @ bits(T[1<<b])), matching the relayout-free bit
    expansion in batched_crc32c_raw."""
    t0 = crc_host._table0()
    # images of the 8 byte-bits when the byte is last in the segment (d = 0)
    rows = np.stack([crc_host._bits_of(int(t0[1 << b])) for b in range(8)])
    a1t = crc_host._advance_one().T.astype(np.int64)
    out = np.zeros((seg, 8, 32), dtype=np.uint8)
    cur = rows.astype(np.int64)
    for d in range(seg):
        out[seg - 1 - d] = cur
        if d + 1 < seg:
            cur = cur @ a1t % 2
    return np.ascontiguousarray(
        out.transpose(1, 0, 2).reshape(8 * seg, 32)).astype(np.int8)


@functools.lru_cache(maxsize=32)
def _tree_matrices(seg: int, nseg: int) -> tuple[np.ndarray, ...]:
    """Transposed advance matrices for each combine level: level k merges
    nodes of seg * 2^k bytes, applying Adv_{seg * 2^k} to the left node."""
    mats = []
    m = nseg
    width = seg
    while m > 1:
        mats.append(crc_host.advance_matrix(width).T.astype(np.int8))
        width *= 2
        m //= 2
    return tuple(mats)


def batched_crc32c_raw(data: jax.Array) -> jax.Array:
    """Raw CRC images g(M) for a batch of chunks.

    data: (..., L) uint8 on device -> (...,) uint32 raw values.  Finalize on
    host with crc32c.finalize_raw(raw, L) to get standard CRC32C.
    Traceable under jit; L is static.
    """
    length = data.shape[-1]
    nseg, seg = _plan_segments(length)
    pad = nseg * seg - length
    with jax.named_scope("ec.crc32c"):
        if pad:
            data = jnp.pad(data, [(0, 0)] * (data.ndim - 1) + [(pad, 0)])
        lead = data.shape[:-1]
        x = data.reshape(*lead, nseg, seg)
        shifts = jnp.arange(8, dtype=jnp.uint8)
        # bit-PLANE-major expansion: (.., nseg, 8, seg) keeps seg
        # minormost, so the merge into (.., nseg, 8*seg) is relayout-free
        # (byte-major order would interleave bit and byte axes and force a
        # full copy of the 8x expanded tensor — measured 6x slower on TPU
        # v5e)
        bits = ((x[..., None, :] >> shifts[:, None]) & 1).astype(jnp.int8)
        bits = bits.reshape(*lead, nseg, 8 * seg)
        w = jnp.asarray(_segment_matrix(seg))  # (8*seg, 32) plane-major
        state = jnp.matmul(bits, w, preferred_element_type=jnp.int32) & 1
        return combine_tree(state, seg, nseg)


def combine_tree(state, seg: int, nseg: int):
    """Fold per-segment raw-CRC bit images into whole-chunk values:
    state (..., nseg, 32) 0/1 -> (...,) uint32.  Level k merges nodes of
    seg * 2^k bytes by advancing the LEFT image over the right's span
    (g(A||B) = Adv_{|B|}(g(A)) ^ g(B)) — shared by the XLA formulation
    above and the fused Pallas kernel (ops/rs_pallas.py)."""
    for advt in _tree_matrices(seg, nseg):
        left = state[..., 0::2, :]
        right = state[..., 1::2, :]
        state = (jnp.matmul(left.astype(jnp.int8), jnp.asarray(advt),
                            preferred_element_type=jnp.int32) & 1) ^ right
    state = state[..., 0, :].astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (state * weights).sum(axis=-1, dtype=jnp.uint32)


def finalize(raw, length: int):
    """Vectorised host finalize: standard CRC32C from raw device values."""
    z = np.uint32(crc_host.crc32c_zeros(length))
    return (np.asarray(raw, dtype=np.uint32) ^ z)

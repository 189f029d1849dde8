"""The plain reference's reconstruction of lost shards, for the cells that
repair a volume: what a rebuilt shard file has to hold, computed with
nothing of the program.

Built from `reference.parity_matrix`, `_gf_invert` and `gf_apply` only.
The 14 x 10 encoding matrix is the identity over the parity rows; the rows
of ten survivors, inverted, give the ten data shards back from them, and
the encoding rows of the lost shards times that inverse give the lost
shards from the survivors in one matrix (klauspost Reconstruct, upstream
`RebuildEcFiles`, ec_encoder.go:233-287).  Any ten survivors give the same
answer: the code is MDS.
"""

from __future__ import annotations

import os
import random

import numpy as np

import reference
from reference import DATA_SHARDS, TOTAL_SHARDS


def encoding_matrix() -> np.ndarray:
    """(14, 10): row i gives shard i from the ten data shards."""
    return np.concatenate([np.eye(DATA_SHARDS, dtype=np.uint8),
                           reference.parity_matrix()])


def reconstruction_matrix(survivors: list[int],
                          lost: list[int]) -> np.ndarray:
    """(len(lost), 10): the lost shards from the ten `survivors`, in the
    order both lists give."""
    if len(survivors) != DATA_SHARDS or set(survivors) & set(lost):
        raise ValueError(f"need {DATA_SHARDS} survivors apart from the "
                         f"lost shards, got {survivors} and {lost}")
    enc = encoding_matrix()
    to_data = reference._gf_invert(enc[survivors])
    return reference.gf_apply(enc[lost], to_data)


def reconstruct(survivors: list[int], rows: np.ndarray,
                lost: list[int]) -> np.ndarray:
    """`rows` (10, L) are the bytes of the `survivors`; returns (len(lost),
    L), the bytes of the `lost` shards at the same offsets."""
    return reference.gf_apply(reconstruction_matrix(survivors, lost), rows)


def check_rebuilt_sample(base: str, lost: list[int], seed: int,
                         sample_bytes: int) -> dict:
    """A seeded sample of 64 KiB columns of a repaired volume: the files
    of the `lost` shards against this file's reconstruction from the
    first ten of the other shard files.  `sample_bytes` counts rebuilt
    bytes.  Returns the bytes that differ and the bytes compared; a
    rebuilt file that is missing or short differs in every byte it
    lacks."""
    survivors = [s for s in range(TOTAL_SHARDS) if s not in lost][
        :DATA_SHARDS]
    shard_size = os.path.getsize(base + reference.shard_ext(survivors[0]))
    piece = 64 << 10
    n_pieces = max(1, min(-(-shard_size // piece),
                          -(-sample_bytes // (len(lost) * piece))))
    offsets = sorted(random.Random(seed).sample(
        range(-(-shard_size // piece)), n_pieces))

    def columns(shard_ids):
        cols = np.zeros((len(shard_ids), n_pieces * piece), dtype=np.uint8)
        got = 0
        for r, sid in enumerate(shard_ids):
            try:
                f = open(base + reference.shard_ext(sid), "rb")
            except OSError:
                continue
            with f:
                for k, off in enumerate(offsets):
                    f.seek(off * piece)
                    got += f.readinto(
                        memoryview(cols[r, k * piece:(k + 1) * piece]))
        return cols, got

    have, _ = columns(survivors)
    rebuilt, got = columns(lost)
    want = reconstruct(survivors, have, lost)
    # the bytes a file lacks read as zero above, and so would the
    # reconstruction of a zero column: count them as differing
    in_file = sum(min(piece, shard_size - off * piece) for off in offsets)
    return {"bytes_differ": int(np.count_nonzero(want != rebuilt))
            + (len(lost) * in_file - got),
            "bytes_compared": len(lost) * in_file}

"""The plain reference of a sealed read: which pieces of which shard files
hold an object's bytes, and which blocks of a lost shard a read of it has
to look up.

`reference.py` says which data shards an extent touches; this adds where in
each shard file, in the order the bytes are joined, and, for a shard that is
gone, the aligned recovery blocks that cover each piece.  Every such block
is one lookup of the program's recovered-block cache (a hit, a miss or a
wait for another reader's miss), so the count is what a run's cache
counters have to add up to.  Upstream's row striping (`ec_locate.go`), no
code of the program: it imports nothing from `seaweedfs_tpu`.
"""

from __future__ import annotations

from reference import DATA_SHARDS, LARGE_BLOCK, SMALL_BLOCK

RECOVER_BLOCK = 256 << 10    # the program's default recovery granularity


def _large_rows(dat_size: int, large_block: int, small_block: int) -> int:
    return (dat_size + DATA_SHARDS * small_block) // (
        large_block * DATA_SHARDS)


def shard_file_size(dat_size: int, large_block: int = LARGE_BLOCK,
                    small_block: int = SMALL_BLOCK) -> int:
    """Bytes of each of the fourteen shard files of a `.dat` of
    `dat_size` bytes: whole large rows, then small rows, the last one
    zero-padded."""
    large_rows = _large_rows(dat_size, large_block, small_block)
    rest = max(0, dat_size - large_rows * large_block * DATA_SHARDS)
    small_rows = -(-rest // (small_block * DATA_SHARDS))
    return large_rows * large_block + small_rows * small_block


def intervals_of_extent(offset: int, length: int, dat_size: int,
                        large_block: int = LARGE_BLOCK,
                        small_block: int = SMALL_BLOCK
                        ) -> list[tuple[int, int, int]]:
    """(data shard, offset in its shard file, bytes) of every piece of
    `.dat` bytes [offset, offset+length), cut at block boundaries, in the
    order of the bytes."""
    large_rows = _large_rows(dat_size, large_block, small_block)
    large_end = large_rows * large_block * DATA_SHARDS
    out = []
    pos, end = offset, offset + length
    while pos < end:
        if pos < large_end:
            block, base, shard_base = large_block, 0, 0
        else:
            block, base, shard_base = (small_block, large_end,
                                       large_rows * large_block)
        index, inner = divmod(pos - base, block)
        take = min(end - pos, block - inner)
        out.append((index % DATA_SHARDS,
                    shard_base + index // DATA_SHARDS * block + inner, take))
        pos += take
    return out


def recovery_blocks(shard_offset: int, length: int, shard_size: int,
                    block: int = RECOVER_BLOCK) -> list[tuple[int, int]]:
    """(start, bytes) of the `block`-aligned pieces of a lost shard file
    that cover [shard_offset, shard_offset+length); the file's last block
    may be short."""
    first = shard_offset // block
    last = (shard_offset + length - 1) // block
    return [(b * block, min(block, shard_size - b * block))
            for b in range(first, last + 1)]


def read_plan(offset: int, length: int, dat_size: int, lost_shards,
              large_block: int = LARGE_BLOCK, small_block: int = SMALL_BLOCK,
              block: int = RECOVER_BLOCK) -> dict:
    """What a read of `.dat` bytes [offset, offset+length) needs while
    `lost_shards` are gone: its `intervals`, those of them on a lost data
    shard (`recovered`: a lost parity shard holds no object's bytes), and
    the recovery `blocks` (shard, start, bytes) looked up for them, one
    entry a lookup."""
    lost = {s for s in lost_shards if s < DATA_SHARDS}
    shard_size = shard_file_size(dat_size, large_block, small_block)
    intervals = intervals_of_extent(offset, length, dat_size, large_block,
                                    small_block)
    recovered = [iv for iv in intervals if iv[0] in lost]
    blocks = [(shard, start, size) for shard, at, n in recovered
              for start, size in recovery_blocks(at, n, shard_size, block)]
    return {"intervals": intervals, "recovered": recovered, "blocks": blocks}

"""Erasure coding: RS(10,4) over striped volume blocks, computed on TPU.

File kinds per volume v (reference weed/storage/erasure_coding/
ec_encoder.go:17-23, ec_volume.go:66-72):
  v.dat/.idx -> v.ec00..v.ec13 (shards), v.ecx (sorted index copy),
  v.ecj (deletion journal), v.vif (volume info sidecar).
"""

DATA_SHARDS_COUNT = 10
PARITY_SHARDS_COUNT = 4
TOTAL_SHARDS_COUNT = DATA_SHARDS_COUNT + PARITY_SHARDS_COUNT
LARGE_BLOCK_SIZE = 1024 * 1024 * 1024  # 1 GB
SMALL_BLOCK_SIZE = 1024 * 1024  # 1 MB


def to_ext(ec_index: int) -> str:
    return f".ec{ec_index:02d}"

"""EC encode/rebuild: volume .dat -> 14 shard files, GF math on TPU.

Layout parity with ec_encoder.go:57-231: the .dat is striped row-major over
10 data shards — repeat 1 GB x 10 rows while more than 10 GB remains, then
1 MB x 10 rows, zero-padding the tail.

TPU-first restructuring: the reference feeds its CPU codec 256 KB-per-shard
batches inside a per-row loop (encodeDataOneBatch).  Because RS parity is
columnwise, any column grouping is equivalent, so here each striped row
becomes a (10, B) byte matrix and large device-sized column chunks are
encoded in single kernel dispatches (Pallas MXU kernel on TPU) —
maximising MXU occupancy and amortising host<->HBM transfers instead of
translating the 256 KB loop.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

from ...ops import codec as codec_mod
from .. import idx as idx_mod
from ..needle_map import load_needle_map_from_idx
from . import (DATA_SHARDS_COUNT, LARGE_BLOCK_SIZE, PARITY_SHARDS_COUNT,
               SMALL_BLOCK_SIZE, TOTAL_SHARDS_COUNT, to_ext)

DEFAULT_CHUNK_BYTES = 64 * 1024 * 1024  # per-shard column chunk per dispatch


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx"):
    """Generate .ecx (ascending-id sorted copy of live .idx entries) —
    WriteSortedFileFromIdx (ec_encoder.go:27-54).  Entries whose latest
    state is a deletion are omitted (readNeedleMap drops them).  Uses the
    compact (numpy) map kind: its vectorised bulk loader keeps .ecx
    generation O(n log n) array work at 100M-needle scale."""
    nm = load_needle_map_from_idx(base_file_name + ".idx", kind="compact")
    with open(base_file_name + ext, "wb") as f:
        for nid, nv in nm.items_ascending():
            if nv.offset > 0 and nv.size >= 0:
                f.write(idx_mod.pack_entry(nid, nv.offset, nv.size))


def _resolve_family(family):
    """Accept a family name, a CodeFamily, or None (-> RS default)."""
    from .codes import get_family

    if hasattr(family, "data_shards"):
        return family
    return get_family(family)


def write_ec_files(base_file_name: str, encoder=None,
                   large_block_size: int = LARGE_BLOCK_SIZE,
                   small_block_size: int = SMALL_BLOCK_SIZE,
                   chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                   batched: Optional[bool] = None,
                   stage_stats: Optional[dict] = None,
                   family=None):
    """Generate .ec00..ec13 from .dat (WriteEcFiles, ec_encoder.go:57-59).

    Default path (no explicit codec): auto-selected by PREDICTED
    throughput on this machine — the streaming batched TPU pipeline
    (parallel/batched_encode.py; device-batched parity with fused CRC32C
    and pipelined host I/O) when the measured host<->device link can
    carry it faster than the host codec, else the host pipeline
    (util/platform.prefer_batched_encode).  Returns the 14 shard-file
    CRC32Cs from the batched paths, None from the host loop.  An explicit
    `encoder` (or batched=False) forces the host loop; batched=True
    forces the device pipeline (-ec.backend=tpu) and raises if the
    device cannot run it.

    stage_stats: optional dict the pipeline that ran fills with its
    backend name, per-stage busy seconds and fractions — see
    parallel/batched_encode._encode_units_device / _encode_units_host.

    family: code-family name or CodeFamily (storage/erasure_coding/codes).
    None / the RS default keeps every path above unchanged; other families
    stripe over their own data-shard count and encode through the family's
    generator on the best host kernel, returning the 14 shard CRC32Cs.
    """
    if family is not None:
        fam = _resolve_family(family)
        if fam.name != "rs_vandermonde":
            return _write_ec_files_family(
                base_file_name, fam, large_block_size, small_block_size,
                chunk_bytes)
    auto_host = False
    if batched is None:
        from ...util.platform import prefer_batched_encode

        batched = encoder is None and prefer_batched_encode()
        auto_host = encoder is None and not batched
    if batched:
        from ...parallel.batched_encode import encode_volumes

        crcs = encode_volumes([base_file_name],
                              large_block=large_block_size,
                              small_block=small_block_size,
                              stage_stats=stage_stats)
        return crcs[base_file_name]
    if auto_host:
        # auto-selection rejected the (link-capped) device path: run the
        # host pipeline — fused GFNI parity+CRC spans with preallocated
        # unbuffered shard writes; inline on a single core (no thread
        # convoy), reader thread + a codec worker per core otherwise —
        # and fused shard CRCs come along for the .vif.
        from ...parallel.batched_encode import encode_volumes

        crcs = encode_volumes([base_file_name],
                              large_block=large_block_size,
                              small_block=small_block_size,
                              host_codec=True,
                              stage_stats=stage_stats)
        return crcs[base_file_name]
    if encoder is None:
        # explicit batched=False: the reference-architecture synchronous
        # host loop, with a genuine host codec (not "auto", which would
        # pick the device backend right back on a TPU machine)
        encoder = codec_mod.new_host_encoder(DATA_SHARDS_COUNT,
                                             PARITY_SHARDS_COUNT)
    if stage_stats is not None:
        stage_stats["backend"] = "host-loop"
    dat_size = os.path.getsize(base_file_name + ".dat")
    outputs = [open(base_file_name + to_ext(i), "wb")
               for i in range(TOTAL_SHARDS_COUNT)]
    try:
        with open(base_file_name + ".dat", "rb") as dat:
            remaining = dat_size
            while remaining > large_block_size * DATA_SHARDS_COUNT:
                _encode_one_row(dat, encoder, large_block_size, outputs,
                                chunk_bytes)
                remaining -= large_block_size * DATA_SHARDS_COUNT
            while remaining > 0:
                _encode_one_row(dat, encoder, small_block_size, outputs,
                                chunk_bytes)
                remaining -= small_block_size * DATA_SHARDS_COUNT
    finally:
        for f in outputs:
            f.close()


def _encode_one_row(dat, encoder, block_size: int, outputs,
                    chunk_bytes: int):
    """Encode one striped row: 10 consecutive blocks -> 14 shard appends."""
    blocks = []
    for _ in range(DATA_SHARDS_COUNT):
        block = dat.read(block_size)
        if len(block) < block_size:
            block = block + b"\x00" * (block_size - len(block))
        blocks.append(np.frombuffer(block, dtype=np.uint8))
    data = np.stack(blocks)  # (10, block_size)
    parity_matrix = encoder.matrix[DATA_SHARDS_COUNT:]
    for start in range(0, block_size, chunk_bytes):
        end = min(start + chunk_bytes, block_size)
        parity = encoder._apply(parity_matrix, data[:, start:end])
        for i in range(DATA_SHARDS_COUNT):
            outputs[i].seek(0, 2)
            outputs[i].write(data[i, start:end].tobytes())
        for i in range(PARITY_SHARDS_COUNT):
            outputs[DATA_SHARDS_COUNT + i].seek(0, 2)
            outputs[DATA_SHARDS_COUNT + i].write(
                np.ascontiguousarray(parity[i]).tobytes())


def _write_ec_files_family(base_file_name: str, fam,
                           large_block_size: int, small_block_size: int,
                           chunk_bytes: int) -> list:
    """Host encode loop for a non-default code family: stripe the .dat
    over the family's k data shards and run its generator on the best
    host GF kernel (the native backend's _apply takes any matrix, so the
    GFNI/AVX2 path serves every family).  Returns the 14 shard CRC32Cs,
    chained as the shards are written — same record the batched RS
    pipeline fuses, so .vif scrub verification works identically."""
    from ...ops.crc32c import crc32c

    fam.check_block(large_block_size)
    fam.check_block(small_block_size)
    chunk_bytes = max(fam.sub_shards,
                      (chunk_bytes // fam.sub_shards) * fam.sub_shards)
    kernel = codec_mod.new_host_encoder(fam.data_shards, fam.parity_shards)
    k = fam.data_shards
    dat_size = os.path.getsize(base_file_name + ".dat")
    outputs = [open(base_file_name + to_ext(i), "wb")
               for i in range(TOTAL_SHARDS_COUNT)]
    crcs = [0] * TOTAL_SHARDS_COUNT
    try:
        with open(base_file_name + ".dat", "rb") as dat:
            remaining = dat_size
            while remaining > 0:
                block_size = (large_block_size
                              if remaining > large_block_size * k
                              else small_block_size)
                blocks = []
                for _ in range(k):
                    block = dat.read(block_size)
                    if len(block) < block_size:
                        block = block + b"\x00" * (block_size - len(block))
                    blocks.append(np.frombuffer(block, dtype=np.uint8))
                data = np.stack(blocks)  # (k, block_size)
                for start in range(0, block_size, chunk_bytes):
                    end = min(start + chunk_bytes, block_size)
                    parity = fam.encode_blocks(data[:, start:end],
                                               apply_fn=kernel._apply)
                    for i in range(k):
                        chunk = data[i, start:end].tobytes()
                        outputs[i].write(chunk)
                        crcs[i] = crc32c(chunk, crcs[i])
                    for i in range(fam.parity_shards):
                        chunk = np.ascontiguousarray(parity[i]).tobytes()
                        outputs[k + i].write(chunk)
                        crcs[k + i] = crc32c(chunk, crcs[k + i])
                remaining -= block_size * k
    finally:
        for f in outputs:
            f.close()
    return crcs


def rebuild_ec_files(base_file_name: str, encoder=None,
                     buffer_size: int = SMALL_BLOCK_SIZE,
                     batched: Optional[bool] = None,
                     family=None, stats: Optional[dict] = None,
                     stage_stats: Optional[dict] = None) -> dict:
    """Regenerate missing .ecNN files from survivors
    (RebuildEcFiles/generateMissingEcFiles, ec_encoder.go:61-118,233-287).
    Returns {shard_id: crc32c-or-None} of the generated shards — CRCs
    come fused from the device path, None from the host loop.

    Default path (no explicit codec): the batched device pipeline —
    survivor chunks stream through one reconstruction bit-matmul with
    fused CRC32C (BASELINE config 3) — when the link can carry it
    faster than the host codec (same auto-selection as write_ec_files).
    An explicit `encoder` or batched=False runs the synchronous host
    loop; batched=True forces the device pipeline (-ec.backend=tpu).

    stage_stats: optional dict filled with the path that ran (backend;
    the device pipeline adds devices, platform, wall and transfer bytes).

    family / stats: a non-default code family (name or CodeFamily), or any
    request for read accounting (stats dict), routes through the planned
    rebuild below — the family's repair planner picks the read set (k
    survivors for MDS decode, d sub-shard projections for pm_msr) instead
    of opening every present shard.
    """
    if family is not None or stats is not None:
        fam = _resolve_family(family)
        if fam.name != "rs_vandermonde" or stats is not None:
            return rebuild_ec_files_planned(base_file_name, fam,
                                            buffer_size, stats)
    if batched is None:
        from ...util.platform import prefer_batched_encode

        batched = encoder is None and prefer_batched_encode()
    if batched:
        from ...parallel.batched_encode import rebuild_shards

        return rebuild_shards(base_file_name, stage_stats=stage_stats)
    if encoder is None:
        encoder = codec_mod.new_host_encoder(DATA_SHARDS_COUNT,
                                             PARITY_SHARDS_COUNT)
    if stage_stats is not None:
        stage_stats["backend"] = "host-loop"
    has_data = [os.path.exists(base_file_name + to_ext(i))
                for i in range(TOTAL_SHARDS_COUNT)]
    generated = [i for i in range(TOTAL_SHARDS_COUNT) if not has_data[i]]
    if not generated:
        return {}
    inputs = {i: open(base_file_name + to_ext(i), "rb")
              for i in range(TOTAL_SHARDS_COUNT) if has_data[i]}
    outputs = {i: open(base_file_name + to_ext(i), "wb") for i in generated}
    try:
        offset = 0
        while True:
            shards: list[Optional[np.ndarray]] = [None] * TOTAL_SHARDS_COUNT
            n = 0
            for i, f in inputs.items():
                f.seek(offset)
                buf = f.read(buffer_size)
                if not buf:
                    return {i: None for i in generated}
                if n == 0:
                    n = len(buf)
                elif len(buf) != n:
                    raise ValueError(
                        f"ec shard size expected {n} actual {len(buf)}")
                shards[i] = np.frombuffer(buf, dtype=np.uint8)
            restored = encoder.reconstruct(shards)
            for i in generated:
                outputs[i].write(np.ascontiguousarray(restored[i]).tobytes())
            offset += n
    finally:
        for f in inputs.values():
            f.close()
        for f in outputs.values():
            f.close()


def rebuild_ec_files_planned(base_file_name: str, fam,
                             buffer_size: int = SMALL_BLOCK_SIZE,
                             stats: Optional[dict] = None) -> dict:
    """Repair-plan-driven rebuild: read only what the family's planner
    asks for.  MDS decode plans read k full survivors (vs every present
    shard in the legacy loop); pm_msr single-shard plans read the d
    helper *projections* — 1/alpha of each helper — which is the
    regenerating-code bandwidth win.  Returns {shard_id: crc32c}; fills
    `stats` with plan kind and read/rebuilt byte counts, where
    read_bytes counts survivor bytes *consumed* (post-projection, i.e.
    what a distributed rebuild moves over the network)."""
    from ...ops.crc32c import crc32c

    a = fam.sub_shards
    buffer_size = max(a, (buffer_size // a) * a)
    has_data = [os.path.exists(base_file_name + to_ext(i))
                for i in range(TOTAL_SHARDS_COUNT)]
    generated = [i for i in range(TOTAL_SHARDS_COUNT) if not has_data[i]]
    present = [i for i in range(TOTAL_SHARDS_COUNT) if has_data[i]]
    out_stats = stats if stats is not None else {}
    out_stats.update({"plan": None, "read_bytes": 0, "rebuilt_bytes": 0,
                      "read_amp": None, "helpers": ()})
    if not generated:
        return {}
    plan = None
    if len(generated) == 1:
        plan = fam.repair_plan(generated[0], present)
    kernel = codec_mod.new_host_encoder(fam.data_shards, fam.parity_shards)
    read_bytes = rebuilt_bytes = 0
    crcs = {i: 0 for i in generated}
    if plan is not None and plan.kind == "projection":
        lost = generated[0]
        inputs = {h: open(base_file_name + to_ext(h), "rb")
                  for h in plan.helpers}
        try:
            with open(base_file_name + to_ext(lost), "wb") as out:
                while True:
                    chunks = []
                    n = None
                    for h in plan.helpers:
                        buf = inputs[h].read(buffer_size)
                        if n is None:
                            n = len(buf)
                        elif len(buf) != n:
                            raise ValueError(
                                f"ec shard size expected {n} "
                                f"actual {len(buf)}")
                        chunks.append(buf)
                    if not n:
                        break
                    projs = np.stack([
                        fam.project(np.frombuffer(c, dtype=np.uint8),
                                    plan.vector) for c in chunks])
                    restored = np.ascontiguousarray(
                        fam.combine_projections(plan, projs)).tobytes()
                    out.write(restored)
                    crcs[lost] = crc32c(restored, crcs[lost])
                    read_bytes += projs.nbytes
                    rebuilt_bytes += n
        finally:
            for f in inputs.values():
                f.close()
    else:
        chosen = (plan.helpers if plan is not None
                  else fam.choose_survivors(present))
        inputs = {i: open(base_file_name + to_ext(i), "rb")
                  for i in chosen}
        outputs = {i: open(base_file_name + to_ext(i), "wb")
                   for i in generated}
        try:
            while True:
                stack = []
                n = None
                for i in chosen:
                    buf = inputs[i].read(buffer_size)
                    if n is None:
                        n = len(buf)
                    elif len(buf) != n:
                        raise ValueError(
                            f"ec shard size expected {n} actual {len(buf)}")
                    stack.append(np.frombuffer(buf, dtype=np.uint8))
                if not n:
                    break
                restored = fam.decode_blocks(chosen, np.stack(stack),
                                             generated,
                                             apply_fn=kernel._apply)
                for idx, i in enumerate(generated):
                    chunk = np.ascontiguousarray(restored[idx]).tobytes()
                    outputs[i].write(chunk)
                    crcs[i] = crc32c(chunk, crcs[i])
                read_bytes += n * len(chosen)
                rebuilt_bytes += n * len(generated)
        finally:
            for f in inputs.values():
                f.close()
            for f in outputs.values():
                f.close()
    out_stats.update({
        "plan": plan.kind if plan is not None else "decode",
        "read_bytes": read_bytes,
        "rebuilt_bytes": rebuilt_bytes,
        "read_amp": (round(read_bytes / rebuilt_bytes, 4)
                     if rebuilt_bytes else None),
        "helpers": (plan.helpers if plan is not None
                    else tuple(sorted(inputs))),
    })
    return crcs


def save_volume_info(base_file_name: str, version: int,
                     extra: Optional[dict] = None):
    """Persist the .vif sidecar (volume_info/volume_info.go) — JSON here
    rather than protobuf; it carries the same version field."""
    info = {"version": version}
    if extra:
        info.update(extra)
    with open(base_file_name + ".vif", "w") as f:
        json.dump(info, f)


def load_volume_info(base_file_name: str) -> Optional[dict]:
    try:
        with open(base_file_name + ".vif") as f:
            return json.load(f)
    except FileNotFoundError:
        return None

"""Store: the volume-server-wide registry of disk locations and volumes.

Parity with weed/storage/store.go:55-73 + store_ec.go: owns DiskLocations,
routes reads/writes/deletes to volumes, assembles heartbeat payloads, and
serves EC reads with the local/remote/reconstruct ladder.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional

from . import types as t
from .disk_location import DiskLocation
from .erasure_coding import encoder as ec_encoder
from .erasure_coding.ec_volume import EcVolume
from .needle import Needle
from .super_block import ReplicaPlacement
from .ttl import TTL
from .volume import NotFoundError, Volume, VolumeError


class Store:
    def __init__(self, directories: list[str],
                 max_volume_counts: Optional[list[int]] = None,
                 ip: str = "127.0.0.1", port: int = 0,
                 public_url: str = "", data_center: str = "",
                 rack: str = "", ec_encoder_backend=None,
                 needle_map_kind: str = "memory", fsync: bool = False):
        counts = max_volume_counts or [8] * len(directories)
        self.locations = [DiskLocation(d, c,
                                       needle_map_kind=needle_map_kind,
                                       fsync=fsync)
                          for d, c in zip(directories, counts)]
        for loc in self.locations:
            loc.load_existing_volumes()
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        self.data_center = data_center
        self.rack = rack
        # master's soft volume size cap, refreshed from each heartbeat
        # response.  As in the reference, the volume server does not reject
        # writes past it (only the 32 GB hard cap applies locally); the
        # master stops assigning to oversized volumes instead
        # (volume_layout.go oversized tracking).
        self.volume_size_limit = 0
        self.lock = threading.RLock()
        self.ec_encoder_backend = ec_encoder_backend
        # called with the vid after a disk-failure read-only demotion so
        # the owning daemon can push a heartbeat immediately (the master
        # must stop assigning writes before the next pulse)
        self.on_demote: Optional[Callable[[int], None]] = None

    @property
    def url(self) -> str:
        return f"{self.ip}:{self.port}"

    # -- lookup ---------------------------------------------------------------
    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                return v
        return None

    def find_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is not None:
                return ev
        return None

    def location_of(self, vid: int) -> Optional[DiskLocation]:
        for loc in self.locations:
            if vid in loc.volumes or vid in loc.ec_volumes:
                return loc
        return None

    def has_volume(self, vid: int) -> bool:
        return self.find_volume(vid) is not None

    # -- volume admin (store.go AddVolume path) -------------------------------
    def add_volume(self, vid: int, collection: str = "",
                   replication: str = "000", ttl: str = ""):
        from .erasure_coding.inline import inline_family_for

        with self.lock:
            if self.find_volume(vid) is not None \
                    or self.find_ec_volume(vid) is not None:
                raise VolumeError(f"volume {vid} already exists")
            loc = max(self.locations, key=lambda l: l.free_slots())
            if loc.free_slots() <= 0:
                raise VolumeError("no free volume slots")
            # assign-time policy: an EC-policy collection with
            # WEED_EC_INLINE=1 gets shard logs as its PRIMARY write
            # path — no .dat, no replica fan-out, no post-hoc encode
            family = inline_family_for(collection)
            if family is not None:
                return loc.add_inline_volume(vid, collection,
                                             family=family)
            return loc.add_volume(
                vid, collection,
                replica_placement=ReplicaPlacement.parse(replication),
                ttl=TTL.parse(ttl))

    def delete_volume(self, vid: int):
        with self.lock:
            for loc in self.locations:
                if vid in loc.volumes:
                    loc.delete_volume(vid)
                    return
                ev = loc.ec_volumes.get(vid)
                if ev is not None and getattr(ev, "writer", None):
                    loc.ec_volumes.pop(vid)
                    ev.destroy()
                    return
            raise NotFoundError(f"volume {vid} not found")

    def mark_volume_readonly(self, vid: int, read_only: bool = True):
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        v.read_only = read_only

    # -- data path ------------------------------------------------------------
    def write_needle(self, vid: int, n: Needle,
                     check_cookie: bool = True) -> tuple[int, bool]:
        v = self.find_volume(vid)
        if v is None:
            ev = self.find_ec_volume(vid)
            if ev is not None and getattr(ev, "writer", None):
                # inline EC volume: the needle streams straight into
                # the striped shard logs, parity follows per stripe
                _, size, unchanged = ev.write_needle(
                    n, check_cookie=check_cookie)
                return size, unchanged
            raise NotFoundError(f"volume {vid} not found")
        try:
            _, size, unchanged = v.write_needle(
                n, check_cookie=check_cookie)
        except OSError as e:
            # a failing disk write demotes the volume to read-only on
            # the spot: reads still serve, the next heartbeat reports
            # read_only and the master stops assigning writes here
            # (store.go MarkVolumeReadonly on write error)
            self._demote_readonly(vid, v, e)
            raise VolumeError(
                f"volume {vid} demoted read-only: "
                f"disk write failed: {e}") from e
        return size, unchanged

    def _demote_readonly(self, vid: int, v: Volume, err: Exception):
        from ..stats import metrics as stats
        from ..util import glog

        try:
            v.read_only = True
        except Exception:
            # even flag persistence may fail on a dead disk; the
            # in-memory flag below is what gates writes
            v._read_only = True
        stats.VolumeReadonlyDemotions.inc()
        glog.errorf("volume %d demoted read-only after disk error: %s",
                    vid, err)
        if self.on_demote is not None:
            try:
                self.on_demote(vid)
            except Exception:
                pass  # heartbeat push is best-effort

    def read_needle(self, vid: int, nid: int,
                    cookie: Optional[int] = None) -> Needle:
        v = self.find_volume(vid)
        if v is not None:
            return v.read_needle(nid, cookie=cookie)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            return ev.read_needle(nid, cookie=cookie)
        raise NotFoundError(f"volume {vid} not found")

    def delete_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is not None:
            return v.delete_needle(n)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            ev.delete_needle(n.id)
            return 0
        raise NotFoundError(f"volume {vid} not found")

    # -- EC admin (volume_grpc_erasure_coding.go handlers) --------------------
    def _resolve_ec_encoder(self):
        """-ec.backend semantics: None or "tpu" select the batched
        device pipeline (encoder=None downstream); a codec NAME
        ("cpu" | "jax" | "numpy") resolves to that host/per-row codec;
        an explicit encoder object passes through."""
        backend = self.ec_encoder_backend
        if backend is None or backend == "tpu":
            return None
        if isinstance(backend, str):
            from ..ops import codec
            from .erasure_coding import (DATA_SHARDS_COUNT,
                                         PARITY_SHARDS_COUNT)

            return codec.new_encoder(DATA_SHARDS_COUNT,
                                     PARITY_SHARDS_COUNT, backend=backend)
        return backend

    def ec_generate(self, vid: int, encoder=None, code_family: str = None,
                    stage_stats: Optional[dict] = None):
        """VolumeEcShardsGenerate: encode a local volume into shard files.
        `stage_stats` (optional dict) is filled by the pipeline that ran:
        its `backend` names the path; the device pipeline adds devices,
        platform and per-stage busy seconds.

        Backend: -ec.backend=tpu forces the streaming batched device
        pipeline; the default (None) auto-selects batched vs host codec
        by predicted throughput on this machine's host<->device link
        (write_ec_files).  Fused per-shard-file CRC32Cs from the batched
        path are persisted in the .vif sidecar for scrub tooling.

        code_family: explicit erasure-code family; None resolves the
        per-collection policy (WEED_EC_CODE[_<COLLECTION>], filer config,
        default RS).  The chosen family is recorded in the .vif so every
        later read/rebuild uses the matrices the shards were cut with.
        """
        from .erasure_coding import codes as ec_codes

        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        family = code_family or ec_codes.family_for_collection(v.collection)
        base = v.file_name()
        v.sync()
        forced = True if (encoder is None
                          and self.ec_encoder_backend == "tpu") else None
        if family != ec_codes.DEFAULT_FAMILY:
            crcs = ec_encoder.write_ec_files(base, family=family)
            if stage_stats is not None:
                stage_stats["backend"] = "host-family-loop"
        else:
            crcs = ec_encoder.write_ec_files(
                base, encoder=encoder or self._resolve_ec_encoder(),
                batched=forced, stage_stats=stage_stats)
        ec_encoder.write_sorted_file_from_idx(base)
        extra = {"code_family": family}
        if crcs:
            extra["shard_crc32c"] = crcs
        ec_encoder.save_volume_info(base, version=v.version, extra=extra)

    def ec_generate_batch(self, vids: list[int]):
        """Batched VolumeEcShardsGenerate: encode MANY local volumes in one
        device pipeline — their row chunks share (B, 10, L) dispatches
        (BASELINE config 4; no reference analogue, per-volume sequential at
        ec_encoder.go:194).  Used when -ec.backend=tpu forces the device
        path or the link-throughput auto-selection predicts the device
        pipeline beats the host codec on this machine."""
        from ..util.platform import prefer_batched_encode

        use_batched = self.ec_encoder_backend == "tpu" or (
            self.ec_encoder_backend is None and prefer_batched_encode())
        if not use_batched:
            enc = self._resolve_ec_encoder()  # resolve the codec ONCE
            for vid in vids:
                self.ec_generate(vid, encoder=enc)
            return
        from ..parallel.batched_encode import encode_volumes
        from .erasure_coding import codes as ec_codes

        vols = []
        for vid in vids:
            v = self.find_volume(vid)
            if v is None:
                raise NotFoundError(f"volume {vid} not found")
            # the shared-dispatch device pipeline speaks the RS layout;
            # collections whose policy picks another family encode
            # per-volume through the family host loop
            if (ec_codes.family_for_collection(v.collection)
                    != ec_codes.DEFAULT_FAMILY):
                self.ec_generate(vid)
                continue
            v.sync()
            vols.append(v)
        if not vols:
            return
        crc_map = encode_volumes([v.file_name() for v in vols])
        for v in vols:
            base = v.file_name()
            ec_encoder.write_sorted_file_from_idx(base)
            ec_encoder.save_volume_info(
                base, version=v.version,
                extra={"shard_crc32c": crc_map[base],
                       "code_family": ec_codes.DEFAULT_FAMILY})

    def ec_rebuild(self, vid: int, collection: str = "",
                   stage_stats: Optional[dict] = None) -> list[int]:
        """VolumeEcShardsRebuild: regenerate missing local shard files.
        -ec.backend=tpu forces the batched device pipeline here exactly
        as it does for ec_generate; `stage_stats` (optional dict) is
        filled with the path that ran.

        When the batched device path produced fused CRCs AND the .vif
        records the original shard CRCs, the rebuilt values are VERIFIED
        against the record — a correct rebuild reproduces the original
        bytes, so a mismatch means a survivor is silently corrupt and the
        rebuild is reported rather than laundered into the record.

        The .vif's code family picks the rebuild path: RS volumes keep
        the legacy device/host pipeline; other families run the planned
        rebuild (the family's repair-optimal read set).  Either way the
        survivor-bytes-per-rebuilt-byte traffic lands in the
        maintenance_ec_rebuild_* metrics, labeled by family."""
        from .erasure_coding import TOTAL_SHARDS_COUNT, to_ext
        from .erasure_coding import codes as ec_codes

        loc = self.location_of(vid)
        base = (loc._base_name(collection, vid) if loc
                else self.locations[0]._base_name(collection, vid))
        info = ec_encoder.load_volume_info(base) or {}
        family = info.get("code_family") or ec_codes.DEFAULT_FAMILY
        if family != ec_codes.DEFAULT_FAMILY:
            rb_stats: dict = {}
            crcs = ec_encoder.rebuild_ec_files(base, family=family,
                                               stats=rb_stats)
            if stage_stats is not None:
                stage_stats["backend"] = "host-planned"
            if rb_stats.get("rebuilt_bytes"):
                ec_codes.note_rebuild(family, rb_stats["read_bytes"],
                                      rb_stats["rebuilt_bytes"])
        else:
            # legacy loop reads every present survivor in full: account
            # the actual traffic from the on-disk sizes
            present_bytes = sum(
                os.path.getsize(base + to_ext(i))
                for i in range(TOTAL_SHARDS_COUNT)
                if os.path.exists(base + to_ext(i)))
            crcs = ec_encoder.rebuild_ec_files(
                base, encoder=self._resolve_ec_encoder(),
                batched=True if self.ec_encoder_backend == "tpu" else None,
                stage_stats=stage_stats)
            rebuilt_bytes = sum(
                os.path.getsize(base + to_ext(sid)) for sid in crcs
                if os.path.exists(base + to_ext(sid)))
            if crcs and rebuilt_bytes:
                ec_codes.note_rebuild(family, present_bytes, rebuilt_bytes)
        stored = info.get("shard_crc32c")
        if isinstance(stored, list) and len(stored) == TOTAL_SHARDS_COUNT:
            bad = [sid for sid, crc in crcs.items()
                   if crc is not None and crc != stored[sid]]
            if bad:
                raise VolumeError(
                    f"rebuilt shards {bad} of volume {vid} do not match "
                    "the recorded CRCs — a survivor shard is corrupt")
        return sorted(crcs)

    def ec_mount(self, collection: str, vid: int, shard_ids: list[int]):
        loc = self.location_of(vid) or self.locations[0]
        for sid in shard_ids:
            loc.mount_ec_shard(collection, vid, sid)

    def ec_unmount(self, vid: int, shard_ids: list[int]):
        for loc in self.locations:
            if vid in loc.ec_volumes:
                for sid in shard_ids:
                    loc.unmount_ec_shard(vid, sid)
                return

    # -- heartbeat assembly (store.go CollectHeartbeat) -----------------------
    def collect_heartbeat(self) -> dict:
        volumes = []
        ec_shards = []
        max_file_key = 0
        max_volume_count = 0
        for loc in self.locations:
            max_volume_count += loc.max_volume_count
            with loc.lock:
                for vid, v in loc.volumes.items():
                    max_file_key = max(max_file_key, v.max_file_key())
                    dat_size, idx_size = v.file_stat()
                    volumes.append({
                        "id": vid,
                        "collection": v.collection,
                        "size": dat_size,
                        "file_count": v.file_count(),
                        "delete_count": v.deleted_count(),
                        "deleted_byte_count": v.deleted_size(),
                        "read_only": v.read_only,
                        "replica_placement":
                            v.super_block.replica_placement.to_byte(),
                        "ttl": v.ttl.to_uint32(),
                        "compact_revision":
                            v.super_block.compaction_revision,
                        "modified_at_second": int(v.last_modified_ts),
                    })
                for vid, ev in loc.ec_volumes.items():
                    if getattr(ev, "writer", None):
                        # inline EC volume: report as a WRITABLE volume
                        # so the master keeps assigning fids to it —
                        # parity is already current, there is nothing
                        # to seal or encode later
                        max_file_key = max(max_file_key,
                                           ev.max_file_key())
                        volumes.append({
                            "id": vid,
                            "collection": ev.collection,
                            "size": ev.writer.logical_size,
                            "file_count": ev.file_count(),
                            "delete_count": ev.deleted_count(),
                            "deleted_byte_count": ev.deleted_size(),
                            "read_only": ev.read_only,
                            "replica_placement": 0,
                            "ttl": 0,
                            "compact_revision": 0,
                            "modified_at_second":
                                int(ev.last_modified_ts),
                        })
                        continue
                    ec_shards.append({
                        "id": vid,
                        "collection": ev.collection,
                        "ec_index_bits": ev.shard_bits().bits,
                    })
        return {
            "ip": self.ip,
            "port": self.port,
            "public_url": self.public_url,
            "data_center": self.data_center,
            "rack": self.rack,
            "max_volume_count": max_volume_count,
            "max_file_key": max_file_key,
            "volumes": volumes,
            "ec_shards": ec_shards,
        }

    def status(self) -> dict:
        hb = self.collect_heartbeat()
        hb["free_slots"] = sum(l.free_slots() for l in self.locations)
        hb["volume_size_limit"] = self.volume_size_limit
        return hb

    def close(self):
        for loc in self.locations:
            loc.close()

"""Seeded volumes, written through the repo's own volume writer.

Loading 1 GB over HTTP took 6 s a volume (PR 21); set-up is paid by every
run of every later check, so the `.dat`/`.idx` are appended directly with
`storage.volume.Volume.write_needle`, the call the volume server's POST
handler ends in.  The bytes and cookie of every object come from the seed.
"""

from __future__ import annotations

import os
import random

import numpy as np

from reference import digest, fid


LAYOUT_SEED = 20260927


def object_sizes(objects: list[dict], vid: int) -> list[int]:
    """The multiset the traffic file states, in one fixed order per
    volume id.  The order decides which objects lie on which shard, and
    with it how much a degraded read has to recover: every seed gets the
    same layout (and other bytes, cookies and read order), so that the
    seed does not change the work."""
    sizes = [o["bytes"] for o in objects for _ in range(o["count"])]
    random.Random(LAYOUT_SEED + vid).shuffle(sizes)
    return sizes


def make_volume(directory: str, collection: str, vid: int,
                objects: list[dict], seed: int,
                with_digests: bool) -> dict:
    """Write one volume; returns its paths and, with digests, the
    reference dict {fid: (needle id, size, blake2b)}."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    rng = np.random.default_rng([seed, vid])
    cookies = random.Random(seed * 1009 + vid)
    vol = Volume(directory, collection, vid)
    written = {}
    try:
        for k, nbytes in enumerate(object_sizes(objects, vid), 1):
            data = rng.bytes(nbytes)
            n = Needle.create(data)
            n.id, n.cookie = k, cookies.getrandbits(32)
            vol.write_needle(n)
            if with_digests:
                written[fid(vid, n.id, n.cookie)] = (n.id, nbytes,
                                                     digest(data))
        vol.sync()
    finally:
        vol.close()
    base = os.path.join(directory, f"{collection}_{vid}")
    return {"vid": vid, "base": base, "dat_bytes": os.path.getsize(
        base + ".dat"), "written": written}


def seal_steps(vid: int, collection: str) -> tuple:
    """`weed shell ec.encode` on one server, as (span name, admin path,
    payload): freeze writes, generate the 14 shards, mount them, drop the
    volume (shell/commands.py ec_encode, without the spread to other
    servers that a one-server deployment has none of)."""
    return (
        ("seal.readonly", "/admin/readonly",
         {"volume": vid, "readonly": True}),
        ("seal.generate", "/admin/ec/generate", {"volume": vid}),
        ("seal.mount", "/admin/ec/mount",
         {"volume": vid, "collection": collection,
          "shard_ids": list(range(14))}),
        ("seal.delete_volume", "/admin/delete_volume", {"volume": vid}),
    )


def link_volume(pristine_base: str, live_base: str):
    """Hard-link a pristine `.dat`/`.idx` into the volume server's
    directory; the server unlinks its own name when the volume is
    dropped and the pristine name keeps the bytes."""
    for ext in (".dat", ".idx"):
        os.link(pristine_base + ext, live_base + ext)

"""The counters of `Volume.lock` (PR 44):
`volumeServer_volume_lock_seconds_total{op,phase}` and
`volumeServer_volume_lock_total{op}`, taken on every acquisition the
served needle methods make, and what a scrape of a volume server shows
of them."""

import os

import pytest

from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc.http_rpc import call
from seaweedfs_tpu.stats import metrics as stats
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import NotFoundError, Volume
from seaweedfs_tpu.volume_server.server import VolumeServer

SECONDS = "SeaweedFS_volumeServer_volume_lock_seconds_total"
COUNT = "SeaweedFS_volumeServer_volume_lock_total"
OPS = ("write", "read", "delete")
PHASES = ("wait", "held")


def _samples():
    """{(family, labels...): value} of the two families and the request
    histogram's sums, from the registry's own exposition."""
    out = {}
    for line in stats.REGISTRY.expose().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        if name.startswith((SECONDS, COUNT,
                            "SeaweedFS_volumeServer_request_seconds_sum")):
            out[name] = float(value)
    return out


@pytest.fixture
def served(tmp_path):
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    before = _samples()
    vs = VolumeServer([str(tmp_path)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    try:
        vs.heartbeat_once()
        yield master, vs, before
    finally:
        vs.stop()
        master.stop()


def test_both_families_stand_in_a_scrape_from_start(served):
    """Every sample is there once the server has started, at what it
    was before (0 in a process that has served nothing): a window
    without a DELETE reads 0 for it, not "no sample"."""
    _, _, before = served
    now = _samples()
    for op in OPS:
        key = f'{COUNT}{{op="{op}"}}'
        assert now[key] == before.get(key, 0.0)
        for phase in PHASES:
            key = f'{SECONDS}{{op="{op}",phase="{phase}"}}'
            assert now[key] == before.get(key, 0.0)


def test_one_uncontended_put_and_get_count_once_and_inside_their_request(
        served):
    master, _, _ = served
    a = call(master.address, "/dir/assign")
    url, fid = a["url"], a["fid"]
    for op, kind, do in (
            ("write", "write", lambda: call(url, f"/{fid}", raw=b"p" * 1024,
                                            method="POST")),
            ("read", "read", lambda: call(url, f"/{fid}", parse=False)),
            ("delete", None, lambda: call(url, f"/{fid}",
                                          method="DELETE"))):
        before = _samples()
        do()
        d = {k: v - before.get(k, 0.0) for k, v in _samples().items()}
        assert d[f'{COUNT}{{op="{op}"}}'] == 1
        wait = d[f'{SECONDS}{{op="{op}",phase="wait"}}']
        held = d[f'{SECONDS}{{op="{op}",phase="held"}}']
        assert wait > 0 and held > 0
        if kind is not None:  # the handler's own timer holds both
            request = d['SeaweedFS_volumeServer_request_seconds_sum'
                        f'{{type="{kind}"}}']
            assert wait < request and held < request
            assert wait + held < request
        for other in OPS:
            if other != op:
                assert d[f'{COUNT}{{op="{other}"}}'] == 0


def test_every_acquisition_of_the_served_methods_is_counted(tmp_path):
    """On the volume itself: a fresh write takes the lock once, an
    overwrite twice (the standing record's lookup, then the append), a
    read and a slice once each, a read that finds nothing once too."""
    v = Volume(str(tmp_path), "", 9)

    def count(op):
        return stats.VolumeLockCounter._values.get((op,), 0.0)

    def needle(data):
        n = Needle.create(data)
        n.id, n.cookie = 5, 0x77
        return n

    try:
        w, r, d = count("write"), count("read"), count("delete")
        v.write_needle(needle(b"one" * 30000))
        assert count("write") - w == 1
        v.write_needle(needle(b"two" * 30000))
        assert count("write") - w == 3
        v.read_needle(5)
        assert count("read") - r == 1
        sliced = v.read_needle_slice(5, 0x77, min_size=1)
        assert sliced is not None
        os.close(sliced[3])
        assert count("read") - r == 2
        with pytest.raises(NotFoundError):
            v.read_needle(6)
        assert count("read") - r == 3
        v.delete_needle(needle(b""))
        assert (count("delete") - d, count("write") - w) == (1, 3)
    finally:
        v.close()

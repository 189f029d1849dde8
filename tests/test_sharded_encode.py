"""Multi-device EC dispatch: the shard_map parity step + fused on-device
CRC vs the single-device and host paths (parallel/mesh.make_parity_step,
which the deep scrub still runs over a whole mesh), and the seal's device
pipeline (parallel/batched_encode), which deals whole batches to the
devices of its mesh in turn: every upload, step and copy back is
single-device.

Runs on the conftest-forced 8-virtual-device CPU backend: the
@multidevice tests build real 4-device meshes, so the shard_map
partitioning, donation, the dealing and per-device pool keying are
exercised in tier-1 without TPU hardware.
"""

import math
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c as crc_host
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.crc_device import finalize
from seaweedfs_tpu.ops.device_pool import get_pool
from seaweedfs_tpu.ops.rs_numpy import NumpyEncoder, gf_apply_matrix
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.parallel.batched_encode import encode_volumes
from seaweedfs_tpu.stats import metrics as stats_mod
from seaweedfs_tpu.storage.erasure_coding import to_ext
from seaweedfs_tpu.storage.erasure_coding.codes import get_family

from test_batched_encode import LARGE, SMALL, _host_reference, _make_volume

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1),
                ("data", "block"))


def _run_step(mesh, matrix, key, data32, fused):
    from seaweedfs_tpu.parallel.mesh import make_parity_step

    p = matrix.shape[0]
    _, b, w = data32.shape
    sh = NamedSharding(mesh, P(None, "data", None))
    step = make_parity_step(mesh, matrix=matrix, key=key, fused_crc=fused)
    out0 = jax.device_put(np.zeros((p, b, w), np.int32), sh)
    din = jax.device_put(data32, sh)
    if fused:
        par, raw = step(din, out0)
        return np.asarray(par), np.asarray(raw)
    return np.asarray(step(din, out0)), None


@pytest.mark.multidevice
class TestShardedParityStep:
    """make_parity_step over a real (4, 1) mesh: byte-equivalence with
    the 1-device step and the numpy codec for every code family — all
    three share the same persistent step, so one parametrized sweep
    covers rs_vandermonde, cauchy and pm_msr generator rows."""

    @pytest.mark.parametrize("fam", ["rs_vandermonde", "cauchy", "pm_msr"])
    @pytest.mark.parametrize("fused", [False, True])
    def test_sharded_matches_single_and_host(self, fam, fused):
        family = get_family(fam)
        matrix = np.ascontiguousarray(family.parity_matrix(),
                                      dtype=np.uint8)
        k_rows = matrix.shape[1]  # data lanes the family consumes
        B, L = 8, 512
        rng = np.random.default_rng(hash((fam, fused)) % 2**32)
        data = rng.integers(0, 256, (k_rows, B, L), dtype=np.uint8)
        d32 = data.view(np.int32).reshape(k_rows, B, L // 4)

        key4 = (fam, "t4", fused)
        key1 = (fam, "t1", fused)
        par4, raw4 = _run_step(_mesh(4), matrix, key4, d32, fused)
        par1, raw1 = _run_step(_mesh(1), matrix, key1, d32, fused)
        assert np.array_equal(par4, par1)

        pbytes = par4.view(np.uint8).reshape(matrix.shape[0], B, L)
        for bi in range(B):
            expect = gf_apply_matrix(matrix, data[:, bi, :])
            assert np.array_equal(pbytes[:, bi, :], expect)
            if fused:
                fin4, fin1 = finalize(raw4, L), finalize(raw1, L)
                assert np.array_equal(fin4, fin1)
                # fused CRC == the host CRC32C walk, byte for byte
                for i in range(k_rows):
                    assert int(fin4[i, bi]) == crc_host.crc32c(data[i, bi])
                for j in range(matrix.shape[0]):
                    assert int(fin4[k_rows + j, bi]) == \
                        crc_host.crc32c(expect[j])

    def test_compacted_k_matches(self):
        """The per-k retrace (trailing zero rows sliced off) holds under
        sharding: k=3 of 10 rows, sharded vs dense host parity."""
        matrix = gf256.parity_matrix(10, 14)
        B, L, k = 8, 256, 3
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, (k, B, L), dtype=np.uint8)
        d32 = data.view(np.int32).reshape(k, B, L // 4)
        par, raw = _run_step(_mesh(4), np.ascontiguousarray(
            matrix, dtype=np.uint8), ("rs", "compact"), d32, True)
        pbytes = par.view(np.uint8).reshape(4, B, L)
        fin = finalize(raw, L)
        dense = np.zeros((10, L), dtype=np.uint8)
        for bi in range(B):
            dense[:k] = data[:, bi, :]
            expect = gf_apply_matrix(matrix, dense)
            assert np.array_equal(pbytes[:, bi, :], expect)
            for j in range(4):
                assert int(fin[k + j, bi]) == crc_host.crc32c(expect[j])


@pytest.mark.multidevice
class TestShardedPipeline:
    """encode_volumes end-to-end on a 4-device mesh: fused and host CRC
    paths both byte-identical to the host reference, across
    padded/masked tails and donation depths."""

    def _encode(self, tmp_path, monkeypatch, sizes, fused, inflight=3):
        monkeypatch.setenv("WEED_EC_DEVICE_SHARD", "4")
        monkeypatch.setenv("WEED_EC_FUSED_CRC", "1" if fused else "0")
        monkeypatch.setenv("WEED_EC_DEVICE_INFLIGHT", str(inflight))
        bases = [_make_volume(tmp_path, f"v{k}", size, 31 * k + size)
                 for k, size in enumerate(sizes)]
        stats = {}
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                              stage_stats=stats)
        return bases, crcs, stats

    def _check(self, tmp_path, bases, crcs):
        for k, base in enumerate(bases):
            ref = _host_reference(tmp_path, base, f"ref{k}")
            for i in range(14):
                with open(base + to_ext(i), "rb") as f:
                    got = f.read()
                with open(ref + to_ext(i), "rb") as f:
                    want = f.read()
                assert got == want, f"vol {k} shard {i}"
                assert crcs[base][i] == crc_host.crc32c(got)

    @pytest.mark.parametrize("fused", [False, True])
    def test_padded_tail_batches(self, tmp_path, monkeypatch, fused):
        # sizes chosen so units end in partial rows and all-padding
        # trailing shard rows (the masked-tail cases: real_rows < 10)
        sizes = [1, SMALL * 3 + 7, SMALL * 10 * 2 + 13, LARGE * 10 + 1]
        bases, crcs, stats = self._encode(tmp_path, monkeypatch, sizes,
                                          fused)
        assert stats["devices"] == 4
        assert stats["backend"].startswith("device-pooled-swar")
        self._check(tmp_path, bases, crcs)

    def test_fused_path_drops_host_crc_stage(self, tmp_path, monkeypatch):
        _, _, fused_stats = self._encode(
            tmp_path, monkeypatch, [SMALL * 10 * 4 + 5], fused=True)
        assert fused_stats["crc_path"] == "fused-device"
        assert "host_crc" not in fused_stats
        _, _, host_stats = self._encode(
            tmp_path, monkeypatch, [SMALL * 10 * 4 + 5], fused=False)
        assert host_stats["crc_path"] == "host"
        assert "host_crc" in host_stats

    @pytest.mark.parametrize("inflight", [1, 4])
    def test_donation_safety_at_depth(self, tmp_path, monkeypatch,
                                      inflight):
        """Donated slots recycle safely at minimum and raised depth: the
        out-ring backpressure must keep a slot's parity alive until the
        completion thread copied it out."""
        sizes = [LARGE * 10 * 2 + 12345, SMALL * 10 * 7 + 13, 999]
        bases, crcs, stats = self._encode(
            tmp_path, monkeypatch, sizes, fused=True, inflight=inflight)
        assert stats["inflight"] == inflight
        self._check(tmp_path, bases, crcs)


def _spy_parity_steps(monkeypatch, calls: list, fail=None):
    """Every parity step the seal builds records (device of its mesh,
    shape and devices of the data it is given, devices of the donated
    slot); `fail(device, n_calls)` true raises in that lane's step."""
    real = mesh_mod.make_parity_step

    def make(mesh, *args, **kwargs):
        (device,) = mesh.devices.flat   # the seal asks for one-device steps
        step = real(mesh, *args, **kwargs)

        def spied(din, out):
            calls.append((device, din.shape, din.devices(), out.devices()))
            if fail is not None and fail(device, len(calls)):
                raise RuntimeError(f"injected into the lane of {device}")
            return step(din, out)
        return spied

    monkeypatch.setattr(mesh_mod, "make_parity_step", make)


def _link_bytes_by_device() -> dict:
    return dict(get_pool().snapshot()["devices"])


def _shard_files(base: str) -> list[bytes]:
    out = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


@pytest.mark.multidevice
class TestDealtBatches:
    """A mesh of N devices is N lanes of the one-device step: batch n
    goes whole to device n mod N.  Results and exact counts."""

    # a large row (100 units), small rows, partial tails and a one-byte
    # volume: 27 batches of 4 units and a tail batch of 3
    SIZES = [LARGE * 10 + SMALL * 10 * 3 + 41, SMALL * 10 * 5 + 7, 1]

    def _seal(self, tmp_path, monkeypatch, n_dev, fused, tag,
              batch_units=16):
        monkeypatch.setenv("WEED_EC_DEVICE_SHARD", str(n_dev))
        monkeypatch.setenv("WEED_EC_FUSED_CRC", "1" if fused else "0")
        bases = [_make_volume(tmp_path, f"{tag}{k}", size, 17 * k + size)
                 for k, size in enumerate(self.SIZES)]
        stats = {}
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                              batch_units=batch_units, stage_stats=stats)
        return bases, crcs, stats

    def _units(self, bases) -> int:
        chunk = be._chunk_len(LARGE, SMALL)
        plans = [be._plan_volume(b, LARGE, SMALL) for b in bases]
        return len(be._make_units(plans, chunk))

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["host-crc", "fused-crc"])
    def test_seal_is_exact_and_every_batch_ran_on_its_device(
            self, tmp_path, monkeypatch, fused):
        calls: list = []
        _spy_parity_steps(monkeypatch, calls)
        devices = jax.devices()[:4]
        before = _link_bytes_by_device()
        bases, crcs, st = self._seal(tmp_path, monkeypatch, 4, fused, "v")

        assert st["backend"] == ("device-pooled-swar-fused-crc" if fused
                                 else "device-pooled-swar")
        assert st["devices"] == 4 and st["platform"] == "cpu"
        assert st["crc_path"] == ("fused-device" if fused else "host")
        # one execution handles batch_units / devices units on one chip
        per_step = st["batch_units"] / st["devices"]
        assert per_step == 4
        n_units = self._units(bases)
        assert st["batches"] == math.ceil(n_units / per_step) == 28
        assert n_units % per_step == 3              # a short tail batch
        assert len(calls) == st["batches"]
        for n, (device, shape, din_on, out_on) in enumerate(calls):
            assert device == devices[n % 4], n      # dealt in turn
            assert din_on == out_on == {device}, n  # single-device arrays
            assert shape[1] == per_step, (n, shape)
        assert st["device_batches"] == [7, 7, 7, 7]
        assert sum(st["device_batches"]) == st["batches"]

        # the link counters: one label a device, together the .dat bytes
        after = _link_bytes_by_device()
        grew = {d: after[d]["h2d_bytes"] - before.get(d, {}).get(
            "h2d_bytes", 0) for d in after}
        assert {d for d, n in grew.items() if n} == {str(d) for d in devices}
        assert sum(grew.values()) >= sum(self.SIZES)
        assert not any(d.startswith("sharded:") and n
                       for d, n in grew.items())
        for d in devices:
            assert stats_mod.EcDeviceH2dBytesCounter._values[
                (str(d),)] >= grew[str(d)]

        # byte- and CRC-exact against the plain numpy codec
        for base in bases:
            got = _shard_files(base)
            full = NumpyEncoder(10, 4).encode(
                [np.frombuffer(s, dtype=np.uint8) for s in got[:10]]
                + [None] * 4)
            assert [np.asarray(p).tobytes() for p in full[10:]] == got[10:]
            assert crcs[base] == [crc_host.crc32c(s) for s in got]
            with open(base + ".dat", "rb") as f:
                dat = f.read()
            striped = b"".join(
                got[i][off:off + blk]
                for _, off, blk in be._plan_volume(base, LARGE, SMALL).rows
                for i in range(10))
            assert striped[:len(dat)] == dat
            assert not any(striped[len(dat):])

    @pytest.mark.parametrize("n_dev", [1, 2, 3, 4])
    def test_batches_differ_by_at_most_one_a_device(
            self, tmp_path, monkeypatch, n_dev):
        """The deal follows the mesh's width, whatever it is: 111 units
        in rounds of 16 (18 over three devices: the quotient is exact),
        and the rest of the count on the first lanes."""
        _, _, st = self._seal(tmp_path, monkeypatch, n_dev, False, "w")
        per_step = math.ceil(16 / n_dev)
        assert st["devices"] == n_dev
        assert st["batch_units"] == per_step * n_dev
        batches = math.ceil(111 / per_step)
        assert st["batches"] == batches
        assert sum(st["device_batches"]) == batches
        assert max(st["device_batches"]) - min(st["device_batches"]) <= 1
        assert st["device_batches"] == sorted(st["device_batches"],
                                              reverse=True)

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["host-crc", "fused-crc"])
    def test_one_device_gives_the_same_files_as_four(
            self, tmp_path, monkeypatch, fused):
        four, crcs4, st4 = self._seal(tmp_path, monkeypatch, 4, fused, "f")
        one, crcs1, st1 = self._seal(tmp_path, monkeypatch, 1, fused, "o")
        assert (st4["devices"], st1["devices"]) == (4, 1)
        assert st1["device_batches"] == [st1["batches"]]
        for b4, b1 in zip(four, one):
            assert _shard_files(b4) == _shard_files(b1)
            assert crcs4[b4] == crcs1[b1]

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["host-crc", "fused-crc"])
    def test_failure_in_one_lane_fails_the_seal_and_frees_the_pool(
            self, tmp_path, monkeypatch, fused):
        calls: list = []
        third = jax.devices()[2]
        # the third lane's second batch (the eleventh of the seal)
        _spy_parity_steps(
            monkeypatch, calls,
            fail=lambda device, n: device == third and n > 4)
        with pytest.raises(RuntimeError, match="injected into the lane"):
            self._seal(tmp_path, monkeypatch, 4, fused, "x")
        assert [c[0] for c in calls].count(third) == 2
        assert len(calls) == 7          # nothing was dealt after it
        snap = get_pool().snapshot()
        assert snap["leased_slots"] == 0
        assert not [t.name for t in threading.enumerate()
                    if t.name.startswith("ec-encode")]
        # and the pool's slabs serve the next seal
        monkeypatch.undo()
        bases, crcs, st = self._seal(tmp_path, monkeypatch, 4, fused, "y")
        assert sum(st["device_batches"]) == st["batches"]
        for base in bases:
            assert crcs[base] == [crc_host.crc32c(s)
                                  for s in _shard_files(base)]

    def test_rings_and_staging_fit_the_pool_on_four_devices(
            self, tmp_path, monkeypatch):
        """At the chip's geometry (1 MiB chunks, the default target
        batch) a four-device seal holds five staging slots of two units
        and one output slot a device: 132 MiB of the pool's 256."""
        monkeypatch.setenv("WEED_EC_DEVICE_SHARD", "4")
        monkeypatch.setenv("WEED_EC_FUSED_CRC", "0")
        monkeypatch.delenv("WEED_EC_DEVICE_INFLIGHT", raising=False)
        monkeypatch.delenv("WEED_EC_DEVICE_POOL_MB", raising=False)
        base = _make_volume(tmp_path, "big", (10 << 20) * 9 + 5, 3)
        pool = get_pool()
        pool.clear()
        evictions = pool.snapshot()["evictions"]
        st = {}
        encode_volumes([base], stage_stats=st)
        assert st["batch_units"] == 8 and st["batches"] == 5
        assert st["device_batches"] == [2, 1, 1, 1]
        assert st["staging_slots"] == 5
        snap = st["pool"]
        assert snap["evictions"] == evictions
        assert snap["bytes"] == 5 * (20 << 20) + 4 * (8 << 20)
        assert snap["bytes"] <= 256 << 20
        pool.clear()


class TestDeviceShardKnob:
    def test_shard_devices_pins_count(self, monkeypatch):
        from seaweedfs_tpu.parallel.mesh import make_ec_mesh, shard_devices

        monkeypatch.setenv("WEED_EC_DEVICE_SHARD", "2")
        assert len(shard_devices()) == 2
        assert make_ec_mesh().devices.shape == (2, 1)

    def test_auto_caps_at_cores_on_cpu(self, monkeypatch):
        from seaweedfs_tpu.parallel.mesh import shard_devices

        monkeypatch.delenv("WEED_EC_DEVICE_SHARD", raising=False)
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:
            cores = os.cpu_count() or 1
        assert len(shard_devices()) == min(len(jax.devices()),
                                           max(1, cores))


if __name__ == "__main__":
    pytest.main([__file__, "-v"])

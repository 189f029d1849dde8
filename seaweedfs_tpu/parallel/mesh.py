"""Multi-chip sharded EC compute: pjit over a (volume, block) device mesh.

The reference has no analogue (per-volume sequential CPU encode,
ec_encoder.go:194-231); this is where the TPU build scales out.  The natural
parallel axes of RS coding:

  * "data"  — the volume/batch axis (independent volumes encode in
    parallel; data-parallel)
  * "block" — the byte-column axis within a shard row (RS parity is
    columnwise, so the L axis shards cleanly; the sequence-parallel
    analogue per SURVEY.md §5.7)

Parity needs no cross-device communication; the fused CRC32C integrity
pass reduces over the sharded block axis, so XLA inserts the collective
over ICI.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf256
from ..ops.rs_jax import _bit_matrix_cached, _matrix_key


def make_mesh(devices=None, axes: tuple[str, str] = ("data", "block")
              ) -> Mesh:
    """Mesh over all devices: batch axis gets the larger factor."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    block = 1
    for cand in (2, 1):
        if n % cand == 0 and n // cand >= 1:
            block = cand
            break
    arr = np.array(devices).reshape(n // block, block)
    return Mesh(arr, axes)


def shard_devices(devices=None) -> list:
    """The device set the EC dispatch path shards batches over, governed
    by WEED_EC_DEVICE_SHARD:

      <int>  — exactly that many devices (clamped to what exists)
      "auto" / unset — every device on real accelerators; on CPU
               backends, min(devices, usable host cores).  XLA's virtual
               CPU devices beyond the physical core count only add
               partitioning overhead, and a 1-device mesh restores the
               zero-copy dlpack H2D path — on a 1-core box "auto"
               collapses the 8-way virtual mesh back to the fast path.
    """
    if devices is None:
        devices = jax.devices()
    raw = os.environ.get("WEED_EC_DEVICE_SHARD", "").strip().lower()
    n = len(devices)
    if raw and raw != "auto":
        try:
            n = max(1, min(len(devices), int(raw)))
        except ValueError:
            pass
    elif devices[0].platform == "cpu":
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:  # pragma: no cover - non-linux
            cores = os.cpu_count() or 1
        n = max(1, min(len(devices), cores))
    return list(devices)[:n]


def aliases_host_memory(device) -> bool:
    """True when numpy -> jax via dlpack is ZERO-copy onto `device`: the
    first CPU device, where from_dlpack lands.  Asked of the device
    itself — asking JAX for the CPU platform's devices raises when only
    the TPU platform is initialised."""
    return device.platform == "cpu" and device.id == 0


def make_ec_mesh(devices=None) -> Mesh:
    """The EC dispatch mesh: shard_devices() laid out (n, 1) — batches
    shard over the "data" axis only.  The fused CRC reduces over a whole
    shard row, so the byte-column ("block") axis stays device-local and
    every per-row CRC completes without a cross-device combine."""
    devs = shard_devices(devices)
    return Mesh(np.array(devs).reshape(-1, 1), ("data", "block"))


def _parity_bits_matmul(bit_matrix, data):
    """(B, d, L) uint8 -> (B, p, L) uint8 parity via MXU bit-matmul."""
    b, d, length = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((data[:, :, None, :] >> shifts[None, None, :, None]) & 1
            ).astype(jnp.int8).reshape(b, d * 8, length)
    prod = jax.lax.dot_general(
        bit_matrix, bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (p*8, B, L)
    out_bits = (prod & 1).astype(jnp.uint8)
    p8 = out_bits.shape[0]
    out_bits = out_bits.reshape(p8 // 8, 8, b, length)
    weights = (jnp.uint8(1) << shifts)[None, :, None, None]
    parity = (out_bits * weights).sum(axis=1, dtype=jnp.uint8)  # (p, B, L)
    return parity.transpose(1, 0, 2)


def batched_swar_encode_step(consts, data):
    """CPU-device variant of the flagship step: SWAR parity (packed
    int32 ops, rs_jax._apply_swar — ~4x the bit-matmul's rate on a CPU
    core, where the 8x bit expansion is pure overhead) + the same fused
    CRC images.  Used by make_sharded_encoder on CPU meshes (the scale-
    validation and virtual-mesh surfaces); TPU meshes keep the MXU
    bit-matmul formulation."""
    from ..ops.crc_device import batched_crc32c_raw
    from ..ops.rs_jax import _apply_swar

    b, d, length = data.shape
    words = jax.lax.bitcast_convert_type(
        data.reshape(b, d, length // 4, 4), jnp.int32)
    out_w = jax.vmap(lambda v: _apply_swar(consts, v, consts.shape[0]))(
        words)
    parity = jax.lax.bitcast_convert_type(out_w, jnp.uint8).reshape(
        b, consts.shape[0], length)
    full = jnp.concatenate([data, parity], axis=1)
    return parity, batched_crc32c_raw(full)


def batched_encode_step(bit_matrix, data):
    """The flagship jittable step: batched parity + fused per-shard CRC32C.

    data: (B, 10, L) uint8 — B independent volume rows.
    Returns (parity (B, 4, L), crc_raw (B, 14) uint32): crc_raw are the raw
    GF(2)-linear CRC32C images of every shard chunk (10 data + 4 parity),
    computed on device by the bit-matmul kernel in ops/crc_device.py while
    the batch is HBM-resident (BASELINE config 5 — the reference CRCs on
    CPU at write time only, needle/crc.go:12-33).  Host side finalizes with
    crc32c.finalize_raw(raw, L) and chains chunks with crc32c_combine.
    """
    from ..ops.crc_device import batched_crc32c_raw

    parity = _parity_bits_matmul(bit_matrix, data)
    full = jnp.concatenate([data, parity], axis=1)  # (B, 14, L)
    crc_raw = batched_crc32c_raw(full)
    return parity, crc_raw


_ENCODER_CACHE: dict = {}
_APPLY_CACHE: dict = {}
_PALLAS_VERIFIED: set = set()
_PARITY_STEP_CACHE: dict = {}


def make_parity_step(mesh: Mesh, data_shards: int = 10,
                     parity_shards: int = 4,
                     matrix=None, key=None, fused_crc: bool = False):
    """Persistent parity step for the pooled device dispatch path:
    (data32 (k, B, W) int32 packed bytes, out (p, B, W) int32 DONATED)
    -> (p, B, W) int32 parity words, plus — with fused_crc — the raw
    CRC32C images (k + p, B) uint32 of every data and parity row,
    computed on device over the same HBM-resident words the parity SWAR
    reads (host side finalizes with crc_device.finalize).

    The k axis is the COMPACTED data-row count: trailing all-zero shard
    rows (the format's zero-padded tail striping) contribute nothing to
    parity, so the caller slices them off and the step retraces per
    distinct k (bounded by data_shards shapes).  The donated `out` slot
    makes XLA alias the result into the same device buffer every batch,
    which is what lets the steady state run with zero per-batch device
    allocations.

    The seal builds the step on one-device meshes only: it deals whole
    batches to the devices of its mesh, a lane each
    (batched_encode._DeviceLane).  Multi-device meshes (the deep scrub's)
    run the step through shard_map: the batch axis
    partitions over "data" with PartitionSpec, every device computes the
    parity (and fused CRC) of its own batch slice, and no collective is
    needed because a shard row's bytes never cross devices (the mesh's
    "block" axis must be 1 when fused_crc is set — the CRC reduces over
    the whole W axis).

    fused_crc=False keeps the CPU-mesh default: the host crc32c kernel
    is ~30x the GF(2) bit-matmul CRC's rate on CPU, so the pipeline CRCs
    on host while the next batch is in flight.  TPU meshes fuse.

    One jitted callable per (mesh, geometry, fused_crc) — one for all
    one-device meshes — shared across encode calls; XLA's shape-keyed
    trace cache handles per-k retraces.

    matrix / key: an alternative GF(2^8) coefficient matrix (a code
    family's parity or lane generator rows) with an optional hashable
    cache identity (e.g. the family name); omitted, the classic RS
    Vandermonde parity rows are built.  Nothing else about the step —
    donation, sharding, the SWAR bit-plane kernel — changes, so every
    code family rides the same persistent jitted dispatch.
    """
    from ..ops.crc_device import batched_crc32c_raw
    from ..ops.rs_jax import _SPREAD, _bit_constants_cached

    # a one-device step runs where its arguments live: one jitted
    # callable, traced once a shape, serves every one-device mesh
    where = mesh if mesh.devices.size > 1 else None
    if matrix is None:
        cache_key = (where, data_shards, parity_shards, fused_crc)
    else:
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        cache_key = (where, key if key is not None else matrix.tobytes(),
                     fused_crc)
    cached = _PARITY_STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    if matrix is None:
        matrix = gf256.parity_matrix(data_shards,
                                     data_shards + parity_shards)
    consts = jnp.asarray(_bit_constants_cached(*_matrix_key(matrix)))
    if fused_crc and mesh.devices.shape[1] != 1:
        raise ValueError(
            "fused-CRC parity step needs a (n, 1) mesh: the CRC reduces "
            f"over the block axis, got mesh shape {mesh.devices.shape}")

    def _parity(data32, out):
        # SWAR over packed words, batched over (B, W): one set bit per
        # byte lane after the shift+mask, so the int32 multiply by the
        # per-bit GF constants stays within each byte (rs_jax._apply_swar
        # generalized to a batch axis, unrolled over k*8 bit planes)
        acc = out ^ out  # zeros that READ the donated slot: keeps the
        #                  buffer aliasable into the result
        for j in range(data32.shape[0]):
            x = data32[j]
            for bit in range(8):
                t = jax.lax.shift_right_logical(x, bit) & _SPREAD
                acc = acc ^ (t[None, :, :] * consts[:, j, bit][:, None, None])
        return acc

    def _fused(data32, out):
        # the scope names the step in a trace whatever this function is
        # called; its Python name is matched by a benchmark pattern too
        with jax.named_scope("ec.encode.fused"):
            parity = _parity(data32, out)
            full = jnp.concatenate([data32, parity], axis=0)  # (k+p, B, W)
            # int32 words -> the row's byte stream: little-endian byte
            # order within a word matches memory order, so the
            # bitcast+reshape is layout-free
            byts = jax.lax.bitcast_convert_type(full, jnp.uint8)
            byts = byts.reshape(full.shape[0], full.shape[1], -1)
            return parity, batched_crc32c_raw(byts)

    body = _fused if fused_crc else _parity
    if mesh.devices.size == 1:
        step = jax.jit(body, donate_argnums=(1,))
    elif fused_crc:
        sh = P(None, "data", None)
        step = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(sh, sh),
                          out_specs=(sh, P(None, "data")), check_vma=False),
            donate_argnums=(1,))
    else:
        sh = P(None, "data", "block")
        step = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(sh, sh), out_specs=sh,
                          check_vma=False),
            donate_argnums=(1,))
    _PARITY_STEP_CACHE[cache_key] = step
    return step


def _pallas_fused_selftest(matrix) -> bool:
    """One-time self-test (per matrix geometry) of the fused Mosaic
    kernel on the TPU: compile+run at a production-representative shape
    (the production fused block with a multi-segment combine) checked
    against the host codec.  Returns True, or raises: a kernel that does
    not compile, or compiles to wrong parity or CRCs, stops the encode
    instead of silently moving production to another path."""
    from ..ops import crc32c as crc_host
    from ..ops.rs_numpy import gf_apply_matrix
    from ..ops.rs_pallas import DEFAULT_FUSED_BLOCK, fused_encode_words

    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    key = (m.tobytes(), m.shape)
    if key in _PALLAS_VERIFIED:
        return True
    rng = np.random.default_rng(0)
    # batch >= 2 so BOTH grid dimensions take nonzero indices on the
    # hardware — a bi>0-only miscompile must not pass the guard;
    # drive the exact production invocation (int32 word views)
    data = rng.integers(0, 256,
                        (2, m.shape[1], 2 * DEFAULT_FUSED_BLOCK),
                        dtype=np.uint8)
    parity_w, crcs = fused_encode_words(m, data.view(np.int32),
                                        interpret=False)
    parity = np.ascontiguousarray(np.asarray(parity_w)).view(np.uint8)
    parity = parity.reshape(data.shape[0], m.shape[0], -1)
    crcs = np.asarray(crcs)
    for bi in range(data.shape[0]):
        expect = gf_apply_matrix(m, data[bi])
        full = np.concatenate([data[bi], expect], axis=0)
        if not (np.array_equal(parity[bi], expect) and all(
                int(crcs[bi, s]) == crc_host.raw_update(
                    0, full[s].tobytes())
                for s in range(full.shape[0]))):
            raise RuntimeError(
                "fused pallas encode self-test MISMATCHED the host codec "
                f"on this TPU (batch element {bi})")
    _PALLAS_VERIFIED.add(key)
    return True


def make_sharded_apply(mesh: Mesh, matrix: np.ndarray):
    """jit-compiled batched GF(2^8) matrix application with fused CRC32C
    over the OUTPUT rows: data (B, d, L) -> (out (B, k, L) uint8,
    crc_raw (B, k) uint32).  The generalization of the encoder step that
    rebuild uses with reconstruction matrices (survivors -> missing
    shards; RebuildEcFiles, ec_encoder.go:233-287)."""
    from ..ops.crc_device import batched_crc32c_raw

    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    cache_key = (mesh, m.tobytes(), m.shape)
    cached = _APPLY_CACHE.get(cache_key)
    if cached is not None:
        return cached
    if len(_APPLY_CACHE) >= 32:
        # bounded: there are C(14,1..4) ~ 1470 distinct reconstruction
        # matrices — unbounded caching would pin a compiled executable
        # per missing-shard pattern forever
        _APPLY_CACHE.pop(next(iter(_APPLY_CACHE)))
    bit_matrix = jnp.asarray(_bit_matrix_cached(*_matrix_key(m)))
    data_sharding = NamedSharding(mesh, P("data", None, "block"))
    out_shardings = (
        NamedSharding(mesh, P("data", None, "block")),
        NamedSharding(mesh, P("data", None)),
    )

    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding,),
        out_shardings=out_shardings,
        donate_argnums=(0,),
    )
    def rebuild_apply(data):
        # a name of its own: on a trace's XLA Modules line the encode
        # step is `jit_step(` and this must not read as it
        with jax.named_scope("ec.rebuild.apply"):
            out = _parity_bits_matmul(bit_matrix, data)
        return out, batched_crc32c_raw(out)

    _APPLY_CACHE[cache_key] = rebuild_apply
    return rebuild_apply


def words_capable(mesh: Mesh, chunk_len: int,
                  data_shards: int = 10, parity_shards: int = 4) -> bool:
    """True when the word-layout fused Pallas step can serve (single
    real-TPU device, fusable chunk length).  The words step moves packed
    int32 views host<->device with NO device bitcasts — the production
    fast path."""
    from ..ops.rs_pallas import fused_encode_block

    matrix = gf256.parity_matrix(data_shards, data_shards + parity_shards)
    return (mesh.devices.size == 1 and chunk_len % 4 == 0
            and bool(fused_encode_block(chunk_len))
            and mesh.devices.flat[0].platform == "tpu"
            and _pallas_fused_selftest(matrix))


def make_sharded_encoder(mesh: Mesh, data_shards: int = 10,
                         parity_shards: int = 4, words: bool = False):
    """jit-compiled batched encoder with shardings over the mesh:
    batch -> "data" axis, byte columns -> "block" axis.  Cached per
    (mesh, geometry, layout) so repeated callers reuse the jit cache
    instead of recompiling every batch.

    words=False — portable XLA formulation on (B, d, L) uint8, which
    GSPMD partitions over multi-device meshes.
    words=True  — the fused word-layout Pallas kernel on (B, d, L//4)
    int32 views (gate with words_capable first): one VMEM bit expansion
    feeds parity AND CRC, packed words move in both directions, and the
    returned parity is (B, p, L//4) int32 to .view(np.uint8) on host."""
    cache_key = (mesh, data_shards, parity_shards, words)
    cached = _ENCODER_CACHE.get(cache_key)
    if cached is not None:
        return cached
    matrix = gf256.parity_matrix(
        data_shards, data_shards + parity_shards)
    bit_matrix = jnp.asarray(_bit_matrix_cached(*_matrix_key(matrix)))

    if words:
        from ..ops.rs_pallas import fused_encode_words

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(data_words):
            with jax.named_scope("ec.encode.step"):
                return fused_encode_words(matrix, data_words,
                                          interpret=False)
    else:
        data_sharding = NamedSharding(mesh, P("data", None, "block"))
        out_shardings = (
            NamedSharding(mesh, P("data", None, "block")),  # parity
            NamedSharding(mesh, P("data", None)),  # crc_raw
        )
        on_cpu_mesh = mesh.devices.flat[0].platform == "cpu"
        consts = None
        if on_cpu_mesh:
            from ..ops.rs_jax import _bit_constants_cached

            consts = jnp.asarray(
                _bit_constants_cached(*_matrix_key(matrix)))

        @functools.partial(
            jax.jit,
            in_shardings=(data_sharding,),
            out_shardings=out_shardings,
            donate_argnums=(0,),
        )
        def step(data):
            # SWAR packs 4 bytes per int32 lane; odd chunk lengths keep
            # the (length-agnostic) bit-matmul formulation
            with jax.named_scope("ec.encode.step"):
                if on_cpu_mesh and data.shape[-1] % 4 == 0:
                    return batched_swar_encode_step(consts, data)
                return batched_encode_step(bit_matrix, data)

    _ENCODER_CACHE[cache_key] = step
    return step


def encode_batch(data: np.ndarray, mesh: Mesh | None = None):
    """Host convenience: shard a (B, 10, L) batch over the mesh and encode.

    Returns (parity (B, 4, L), crcs (B, 14) uint32) with the device CRC32C
    values finalized to standard form (crc32c of each shard chunk).
    """
    from ..ops.crc_device import finalize

    if mesh is None:
        mesh = make_mesh()
    data = np.ascontiguousarray(data).astype(np.uint8, copy=False)
    b, d, length = data.shape
    if words_capable(mesh, length):
        step = make_sharded_encoder(mesh, words=True)
        parity_w, crc_raw = step(jax.device_put(data.view(np.int32),
                                                mesh.devices.flat[0]))
        parity = np.ascontiguousarray(np.asarray(parity_w)) \
            .view(np.uint8).reshape(b, -1, length)
        return parity, finalize(crc_raw, length)
    step = make_sharded_encoder(mesh)
    sharding = NamedSharding(mesh, P("data", None, "block"))
    device_data = jax.device_put(jnp.asarray(data, dtype=jnp.uint8),
                                 sharding)
    parity, crc_raw = step(device_data)
    return np.asarray(parity), finalize(crc_raw, data.shape[-1])

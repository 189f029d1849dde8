// Native acceleration for seaweedfs_tpu's host-side paths.
//
// Components:
//  1. CRC32C (Castagnoli) — the needle checksum the reference computes with
//     Go's hash/crc32 Castagnoli table (reference:
//     /root/reference/weed/storage/needle/crc.go:12-33).  SSE4.2 hardware
//     CRC when available, slicing-by-8 tables otherwise.
//  2. GF(2^8) matrix application — the CPU Reed-Solomon codec.  Kernel
//     ladder, best-first at runtime:
//       * GFNI + AVX-512: GF2P8AFFINEQB with multiply-by-constant affine
//         matrices, 4 output rows per data pass, 256 B column blocks.
//         Same instruction class as klauspost/reedsolomon's newest
//         galois_gen kernels; ~15 GiB/s (data rate) per core on
//         cache-resident chunks, ~7 GiB/s streaming.
//       * GFNI + AVX2 (VEX 256-bit) for GFNI cores without AVX-512.
//       * AVX2 PSHUFB on 16-entry nibble product tables — the
//         klauspost-classic kernel; the one that runs on a core
//         without GFNI, and callable on any core through
//         sw_gf_apply_matrix_force, which the tests use to hold every
//         level to the NumPy reference.
//       * scalar table lookups.
//  3. sw_encode_rows — fused span encode: parity plus CRC32C of every
//     data+parity shard in ONE call, affine+CRC interleaved in 128 KiB
//     cache-resident column blocks, so the Python pipeline drops the
//     GIL once per multi-row span and the CRC pass is nearly free.
//
// Built as a plain shared library; Python binds via ctypes (no pybind11 in
// this image).

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <cstddef>
#include <unistd.h>

#if defined(__x86_64__)
#include <immintrin.h>
#include <nmmintrin.h>
#endif

extern "C" {

// ---------------------------------------------------------------------------
// CRC32C
// ---------------------------------------------------------------------------

// Thread-safe lazy init via C++11 magic statics (ctypes calls drop the GIL,
// so first use can race across Python threads).
struct Crc32cTables {
    uint32_t t[8][256];
    Crc32cTables() {
        const uint32_t poly = 0x82F63B78u;  // reflected Castagnoli
        for (uint32_t i = 0; i < 256; i++) {
            uint32_t crc = i;
            for (int j = 0; j < 8; j++)
                crc = (crc >> 1) ^ ((crc & 1) ? poly : 0);
            t[0][i] = crc;
        }
        for (uint32_t i = 0; i < 256; i++) {
            uint32_t crc = t[0][i];
            for (int s = 1; s < 8; s++) {
                crc = t[0][crc & 0xFF] ^ (crc >> 8);
                t[s][i] = crc;
            }
        }
    }
};

static const uint32_t (*crc32c_tables())[256] {
    static const Crc32cTables tables;
    return tables.t;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t* data, size_t len) {
    const uint32_t (*crc32c_table)[256] = crc32c_tables();
    crc = ~crc;
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, data, 8);
        word ^= (uint64_t)crc;
        crc = crc32c_table[7][word & 0xFF] ^
              crc32c_table[6][(word >> 8) & 0xFF] ^
              crc32c_table[5][(word >> 16) & 0xFF] ^
              crc32c_table[4][(word >> 24) & 0xFF] ^
              crc32c_table[3][(word >> 32) & 0xFF] ^
              crc32c_table[2][(word >> 40) & 0xFF] ^
              crc32c_table[1][(word >> 48) & 0xFF] ^
              crc32c_table[0][(word >> 56) & 0xFF];
        data += 8;
        len -= 8;
    }
    while (len--) crc = crc32c_table[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
    return ~crc;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(uint32_t crc, const uint8_t* data, size_t len) {
    uint64_t c = ~crc;
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, data, 8);
        c = _mm_crc32_u64(c, word);
        data += 8;
        len -= 8;
    }
    while (len--) c = _mm_crc32_u8((uint32_t)c, *data++);
    return ~(uint32_t)c;
}
#endif

uint32_t sw_crc32c(uint32_t crc, const uint8_t* data, size_t len) {
#if defined(__x86_64__)
    if (__builtin_cpu_supports("sse4.2")) return crc32c_hw(crc, data, len);
#endif
    return crc32c_sw(crc, data, len);
}

// ---------------------------------------------------------------------------
// GF(2^8) — field 0x11D, matching klauspost/reedsolomon & Backblaze
// ---------------------------------------------------------------------------

struct GfTables {
    uint8_t mul[256][256];
    GfTables() {
        uint8_t exp_t[510];
        int log_t[256] = {0};
        int x = 1;
        for (int i = 0; i < 255; i++) {
            exp_t[i] = (uint8_t)x;
            log_t[x] = i;
            x <<= 1;
            if (x & 0x100) x ^= 0x11D;
        }
        for (int i = 255; i < 510; i++) exp_t[i] = exp_t[i - 255];
        for (int a = 0; a < 256; a++)
            for (int b = 0; b < 256; b++)
                mul[a][b] = (a && b) ? exp_t[log_t[a] + log_t[b]] : 0;
    }
};

static const uint8_t (*gf_mul_tables())[256] {
    static const GfTables tables;
    return tables.mul;
}

static void gf_apply_row_scalar(const uint8_t* coeffs, int d,
                                const uint8_t* data, size_t len,
                                uint8_t* out) {
    const uint8_t (*gf_mul_table)[256] = gf_mul_tables();
    memset(out, 0, len);
    for (int j = 0; j < d; j++) {
        const uint8_t* table = gf_mul_table[coeffs[j]];
        const uint8_t* in = data + (size_t)j * len;
        for (size_t k = 0; k < len; k++) out[k] ^= table[in[k]];
    }
}

#if defined(__x86_64__)
// klauspost-style AVX2 kernel: per coefficient, 16-entry low/high nibble
// product tables applied with VPSHUFB, XOR-accumulated across input shards.
__attribute__((target("avx2")))
static void gf_apply_row_avx2(const uint8_t* coeffs, int d,
                              const uint8_t* data, size_t len,
                              uint8_t* out) {
    size_t vec_len = len & ~(size_t)31;
    const uint8_t (*gf_mul_table)[256] = gf_mul_tables();
    __m256i low_mask = _mm256_set1_epi8(0x0F);
    memset(out, 0, len);
    for (int j = 0; j < d; j++) {
        uint8_t c = coeffs[j];
        const uint8_t* table = gf_mul_table[c];
        alignas(32) uint8_t lo[32], hi[32];
        for (int t = 0; t < 16; t++) {
            lo[t] = lo[t + 16] = table[t];
            hi[t] = hi[t + 16] = table[t << 4];
        }
        __m256i vlo = _mm256_load_si256((const __m256i*)lo);
        __m256i vhi = _mm256_load_si256((const __m256i*)hi);
        const uint8_t* in = data + (size_t)j * len;
        for (size_t k = 0; k < vec_len; k += 32) {
            __m256i v = _mm256_loadu_si256((const __m256i*)(in + k));
            __m256i vl = _mm256_and_si256(v, low_mask);
            __m256i vh = _mm256_and_si256(_mm256_srli_epi64(v, 4), low_mask);
            __m256i prod = _mm256_xor_si256(_mm256_shuffle_epi8(vlo, vl),
                                            _mm256_shuffle_epi8(vhi, vh));
            __m256i acc = _mm256_loadu_si256((const __m256i*)(out + k));
            _mm256_storeu_si256((__m256i*)(out + k),
                                _mm256_xor_si256(acc, prod));
        }
        for (size_t k = vec_len; k < len; k++) out[k] ^= table[in[k]];
    }
}
#endif

#if defined(__x86_64__)
// ---------------------------------------------------------------------------
// GFNI kernels.  GF2P8AFFINEQB computes, per byte, an 8x8 GF(2) bit-matrix
// product — polynomial-agnostic, unlike GF2P8MULB (which is fixed to the
// AES field 0x11B and thus useless for RS 0x11D).  Multiplication by a
// constant c in GF(2^8)/0x11D is GF(2)-linear, so it is exactly one affine
// matrix: row i (= result bit i) has bit j set iff bit i of mul(c, 1<<j).
// Intel's layout wants row i in byte 7-i of the qword.
// ---------------------------------------------------------------------------
static uint64_t gfni_matrix(uint8_t c) {
    const uint8_t (*mt)[256] = gf_mul_tables();
    uint64_t A = 0;
    for (int i = 0; i < 8; i++) {
        uint8_t row = 0;
        for (int j = 0; j < 8; j++)
            if ((mt[c][1u << j] >> i) & 1) row |= (uint8_t)(1u << j);
        A |= (uint64_t)row << (8 * (7 - i));
    }
    return A;
}

static void gfni_matrices(const uint8_t* matrix, int p, int d,
                          uint64_t* aff) {
    for (int i = 0; i < p * d; i++) aff[i] = gfni_matrix(matrix[i]);
}

// Row-grouped: up to 4 output rows share one pass over the data shards, so
// for RS(10,4) the data is streamed from memory ONCE (the PSHUFB kernel
// below streams it once per row).  256 B column blocks keep 16 zmm
// accumulators + 4 data registers live.
__attribute__((target("gfni,avx512f,avx512bw,avx512vl")))
static void gf_apply_gfni512(const uint64_t* aff, const uint8_t* mrows,
                             int p, int d, const uint8_t* data, size_t len,
                             uint8_t* out, size_t in_stride,
                             size_t out_stride) {
    const uint8_t (*mt)[256] = gf_mul_tables();
    for (int i0 = 0; i0 < p; i0 += 4) {
        int pg = (p - i0 < 4) ? (p - i0) : 4;
        size_t k = 0;
        for (; k + 256 <= len; k += 256) {
            __m512i acc[4][4];
            for (int i = 0; i < pg; i++)
                for (int u = 0; u < 4; u++)
                    acc[i][u] = _mm512_setzero_si512();
            for (int j = 0; j < d; j++) {
                const uint8_t* in = data + (size_t)j * in_stride + k;
                __m512i v0 = _mm512_loadu_si512(in);
                __m512i v1 = _mm512_loadu_si512(in + 64);
                __m512i v2 = _mm512_loadu_si512(in + 128);
                __m512i v3 = _mm512_loadu_si512(in + 192);
                for (int i = 0; i < pg; i++) {
                    __m512i m = _mm512_set1_epi64(aff[(i0 + i) * d + j]);
                    acc[i][0] = _mm512_xor_si512(
                        acc[i][0], _mm512_gf2p8affine_epi64_epi8(v0, m, 0));
                    acc[i][1] = _mm512_xor_si512(
                        acc[i][1], _mm512_gf2p8affine_epi64_epi8(v1, m, 0));
                    acc[i][2] = _mm512_xor_si512(
                        acc[i][2], _mm512_gf2p8affine_epi64_epi8(v2, m, 0));
                    acc[i][3] = _mm512_xor_si512(
                        acc[i][3], _mm512_gf2p8affine_epi64_epi8(v3, m, 0));
                }
            }
            for (int i = 0; i < pg; i++)
                for (int u = 0; u < 4; u++)
                    _mm512_storeu_si512(
                        out + (size_t)(i0 + i) * out_stride + k + 64 * u,
                        acc[i][u]);
        }
        for (; k + 64 <= len; k += 64) {
            for (int i = 0; i < pg; i++) {
                __m512i a = _mm512_setzero_si512();
                for (int j = 0; j < d; j++) {
                    __m512i v = _mm512_loadu_si512(
                        data + (size_t)j * in_stride + k);
                    __m512i m = _mm512_set1_epi64(aff[(i0 + i) * d + j]);
                    a = _mm512_xor_si512(
                        a, _mm512_gf2p8affine_epi64_epi8(v, m, 0));
                }
                _mm512_storeu_si512(out + (size_t)(i0 + i) * out_stride + k, a);
            }
        }
        for (; k < len; k++) {
            for (int i = 0; i < pg; i++) {
                uint8_t a = 0;
                for (int j = 0; j < d; j++)
                    a ^= mt[mrows[(i0 + i) * d + j]]
                          [data[(size_t)j * in_stride + k]];
                out[(size_t)(i0 + i) * out_stride + k] = a;
            }
        }
    }
}

// VEX 256-bit variant for GFNI cores without usable AVX-512.
__attribute__((target("gfni,avx2")))
static void gf_apply_gfni256(const uint64_t* aff, const uint8_t* mrows,
                             int p, int d, const uint8_t* data, size_t len,
                             uint8_t* out, size_t in_stride,
                             size_t out_stride) {
    const uint8_t (*mt)[256] = gf_mul_tables();
    for (int i0 = 0; i0 < p; i0 += 4) {
        int pg = (p - i0 < 4) ? (p - i0) : 4;
        size_t k = 0;
        for (; k + 128 <= len; k += 128) {
            __m256i acc[4][4];
            for (int i = 0; i < pg; i++)
                for (int u = 0; u < 4; u++)
                    acc[i][u] = _mm256_setzero_si256();
            for (int j = 0; j < d; j++) {
                const uint8_t* in = data + (size_t)j * in_stride + k;
                __m256i v0 = _mm256_loadu_si256((const __m256i*)in);
                __m256i v1 = _mm256_loadu_si256((const __m256i*)(in + 32));
                __m256i v2 = _mm256_loadu_si256((const __m256i*)(in + 64));
                __m256i v3 = _mm256_loadu_si256((const __m256i*)(in + 96));
                for (int i = 0; i < pg; i++) {
                    __m256i m = _mm256_set1_epi64x(
                        (long long)aff[(i0 + i) * d + j]);
                    acc[i][0] = _mm256_xor_si256(
                        acc[i][0], _mm256_gf2p8affine_epi64_epi8(v0, m, 0));
                    acc[i][1] = _mm256_xor_si256(
                        acc[i][1], _mm256_gf2p8affine_epi64_epi8(v1, m, 0));
                    acc[i][2] = _mm256_xor_si256(
                        acc[i][2], _mm256_gf2p8affine_epi64_epi8(v2, m, 0));
                    acc[i][3] = _mm256_xor_si256(
                        acc[i][3], _mm256_gf2p8affine_epi64_epi8(v3, m, 0));
                }
            }
            for (int i = 0; i < pg; i++)
                for (int u = 0; u < 4; u++)
                    _mm256_storeu_si256(
                        (__m256i*)(out + (size_t)(i0 + i) * out_stride + k +
                                   32 * u),
                        acc[i][u]);
        }
        for (; k < len; k++) {
            for (int i = 0; i < pg; i++) {
                uint8_t a = 0;
                for (int j = 0; j < d; j++)
                    a ^= mt[mrows[(i0 + i) * d + j]]
                          [data[(size_t)j * in_stride + k]];
                out[(size_t)(i0 + i) * out_stride + k] = a;
            }
        }
    }
}
#endif  // __x86_64__

// Kernel ladder levels (sw_cpu_level / sw_gf_apply_matrix_force).
enum { GF_SCALAR = 0, GF_AVX2 = 1, GF_GFNI256 = 2, GF_GFNI512 = 3 };

static int gf_best_level() {
#if defined(__x86_64__)
    if (__builtin_cpu_supports("gfni")) {
        if (__builtin_cpu_supports("avx512bw") &&
            __builtin_cpu_supports("avx512vl"))
            return GF_GFNI512;
        if (__builtin_cpu_supports("avx2")) return GF_GFNI256;
    }
    if (__builtin_cpu_supports("avx2")) return GF_AVX2;
#endif
    return GF_SCALAR;
}

static void gf_apply_matrix_level(const uint8_t* matrix, int p, int d,
                                  const uint8_t* data, size_t len,
                                  uint8_t* out, int level) {
    (void)gf_mul_tables();  // ensure tables exist before dispatch
#if defined(__x86_64__)
    if (level >= GF_GFNI256 && p <= 64) {
        uint64_t aff[64 * 32];
        if (p * d <= (int)(sizeof(aff) / sizeof(aff[0]))) {
            gfni_matrices(matrix, p, d, aff);
            if (level == GF_GFNI512)
                gf_apply_gfni512(aff, matrix, p, d, data, len, out,
                                 len, len);
            else
                gf_apply_gfni256(aff, matrix, p, d, data, len, out,
                                 len, len);
            return;
        }
        level = GF_AVX2;  // coefficient matrix too large to pre-affine
    }
    if (level == GF_AVX2) {
        for (int i = 0; i < p; i++)
            gf_apply_row_avx2(matrix + (size_t)i * d, d, data, len,
                              out + (size_t)i * len);
        return;
    }
#endif
    for (int i = 0; i < p; i++)
        gf_apply_row_scalar(matrix + (size_t)i * d, d, data, len,
                            out + (size_t)i * len);
}

// out[i*len .. ] = XOR_j gf_mul(matrix[i*d+j], data[j*len ..])
void sw_gf_apply_matrix(const uint8_t* matrix, int p, int d,
                        const uint8_t* data, size_t len, uint8_t* out) {
    gf_apply_matrix_level(matrix, p, d, data, len, out, gf_best_level());
}

// Pin a specific kernel level (the tests' ladder check); level -1 = auto.
// Levels above the machine's capability clamp down to the best available.
void sw_gf_apply_matrix_force(const uint8_t* matrix, int p, int d,
                              const uint8_t* data, size_t len, uint8_t* out,
                              int level) {
    int best = gf_best_level();
    if (level < 0 || level > best) level = best;
    gf_apply_matrix_level(matrix, p, d, data, len, out, level);
}

int sw_cpu_level() { return gf_best_level(); }

// Fused multi-row encode: `rows` consecutive striped rows in one call.
// data: (rows, d, len) contiguous; parity out: (rows, p, len); crcs:
// d+p uint32s, SEEDED by the caller and chained across the rows (row r's
// shard-j bytes continue shard j's rolling CRC32C — consecutive rows are
// adjacent in the shard file, so the chain IS the file CRC).  Each row's
// affine pass is followed immediately by its CRC pass while the row is
// cache-resident; the whole span costs one ctypes call (one GIL drop).
void sw_encode_rows(const uint8_t* matrix, int p, int d,
                    const uint8_t* data, size_t len, int rows,
                    uint8_t* parity, uint32_t* crcs) {
#if defined(__x86_64__)
    int level = gf_best_level();
    if (level >= GF_GFNI256 && p <= 64 &&
        p * d <= 64 * 32) {
        // cache-blocked fusion: affine + CRC in 128 KiB column blocks,
        // so the CRC pass reads L2-resident bytes instead of re-
        // streaming the whole row from memory (the row's 14 MB working
        // set does not survive to a second pass).  Per-shard CRCs chain
        // across blocks and rows — the chain IS the file CRC.
        uint64_t aff[64 * 32];
        gfni_matrices(matrix, p, d, aff);
        const size_t BLK = (size_t)128 << 10;
        for (int r = 0; r < rows; r++) {
            const uint8_t* dr = data + (size_t)r * d * len;
            uint8_t* pr = parity + (size_t)r * p * len;
            for (size_t c = 0; c < len; c += BLK) {
                size_t b = len - c < BLK ? len - c : BLK;
                if (level == GF_GFNI512)
                    gf_apply_gfni512(aff, matrix, p, d, dr + c, b,
                                     pr + c, len, len);
                else
                    gf_apply_gfni256(aff, matrix, p, d, dr + c, b,
                                     pr + c, len, len);
                for (int j = 0; j < d; j++)
                    crcs[j] = sw_crc32c(crcs[j],
                                        dr + (size_t)j * len + c, b);
                for (int i = 0; i < p; i++)
                    crcs[d + i] = sw_crc32c(
                        crcs[d + i], pr + (size_t)i * len + c, b);
            }
        }
        return;
    }
#endif
    for (int r = 0; r < rows; r++) {
        const uint8_t* dr = data + (size_t)r * d * len;
        uint8_t* pr = parity + (size_t)r * p * len;
        sw_gf_apply_matrix(matrix, p, d, dr, len, pr);
        for (int j = 0; j < d; j++)
            crcs[j] = sw_crc32c(crcs[j], dr + (size_t)j * len, len);
        for (int i = 0; i < p; i++)
            crcs[d + i] = sw_crc32c(crcs[d + i], pr + (size_t)i * len, len);
    }
}


int sw_has_avx2() {
#if defined(__x86_64__)
    return __builtin_cpu_supports("avx2") ? 1 : 0;
#else
    return 0;
#endif
}

// ---------------------------------------------------------------------------
// 4. sw_inline_scatter — the inline-EC append hot path.  Scatters one
//    logical byte range over the k data-shard logs in stripe-unit
//    blocks (block i -> shard i%k at offset (i/k)*unit — the zero-
//    large-row regime of storage/erasure_coding/locate.py), issuing
//    every pwrite from C so the Python writer drops the GIL exactly
//    once per needle instead of once per shard segment.
//    Returns 0 on success, -errno on the first failed write.

int sw_inline_scatter(const int32_t* fds, int32_t k, uint64_t unit,
                      uint64_t offset, const uint8_t* blob, uint64_t len) {
    uint64_t pos = 0;
    while (pos < len) {
        uint64_t block = (offset + pos) / unit;
        uint64_t inner = (offset + pos) % unit;
        uint64_t sid = block % (uint64_t)k;
        uint64_t shard_off = (block / (uint64_t)k) * unit + inner;
        uint64_t take = len - pos;
        if (take > unit - inner) take = unit - inner;
        const uint8_t* p = blob + pos;
        uint64_t left = take;
        while (left > 0) {
            ssize_t w = pwrite(fds[sid], p, left, (off_t)shard_off);
            if (w < 0) {
                if (errno == EINTR) continue;
                return -errno;
            }
            p += w;
            shard_off += (uint64_t)w;
            left -= (uint64_t)w;
        }
        pos += take;
    }
    return 0;
}

}  // extern "C"

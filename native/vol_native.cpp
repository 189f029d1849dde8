// Native volume engine: the hot data plane of the volume server in C++.
//
// The reference's volume server is compiled Go; its published headline
// benchmark (15.7k writes/s, 47k reads/s on one laptop core —
// /root/reference/README.md:342-391) is unreachable from a GIL-bound
// Python handler loop.  This engine moves the per-request path of the
// storage engine out of Python:
//
//  1. Needle index (weed/storage/needle_map/compact_map.go semantics):
//     an open-addressing u64->(offset,size) map with the reference's
//     deletion convention (entries keep a negated size so reads can
//     distinguish deleted from absent) plus the counter set the
//     heartbeat reports (file/deleted counts and byte totals).
//  2. Append path (volume_write.go:109-231): serialized appends to the
//     .dat with the 16-byte big-endian .idx entry log
//     (weed/storage/idx/walk.go:12-50), cookie checks against the
//     existing needle, identical-rewrite dedup, and tombstone deletes.
//  3. A framed-TCP server speaking the framework's fast-path protocol
//     (G/W/D lines + >II status/len replies — the same wire format the
//     Python TCP fast path serves, so VolumeTcpClient works unchanged)
//     with request handling entirely off the GIL.
//  4. A load-generator (svn_bench) so the benchmark harness can drive
//     the server at native speed, like the reference's compiled Go
//     `weed benchmark` client (weed/command/benchmark.go:27-90).
//
// Python (storage/native_engine.py) keeps the control plane: volume
// lifecycle, vacuum, EC, replication and HTTP stay in the daemon; both
// sides share this index and append path, so each is always coherent
// with writes made by the other.
//
// Needle layouts mirrored here: weed/storage/needle/needle_write.go:20-113
// (v1/v2/v3), CRC32C over data only (needle/crc.go:12-33, legacy rotated
// Value() accepted on read).

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <random>
#include <shared_mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include <arpa/inet.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <unistd.h>

#if defined(__x86_64__)
#include <nmmintrin.h>
#endif

#include <zlib.h>

namespace {

// ---------------------------------------------------------------------------
// CRC32C (Castagnoli) — same dispatch as ec_native.cpp
// ---------------------------------------------------------------------------

struct Crc32cTables {
    uint32_t t[8][256];
    Crc32cTables() {
        const uint32_t poly = 0x82F63B78u;
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

uint32_t crc32c_sw_impl(uint32_t crc, const uint8_t* data, size_t len) {
    static const Crc32cTables tables;
    const uint32_t(*t)[256] = tables.t;
    crc = ~crc;
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, data, 8);
        word ^= (uint64_t)crc;
        crc = t[7][word & 0xFF] ^ t[6][(word >> 8) & 0xFF] ^
              t[5][(word >> 16) & 0xFF] ^ t[4][(word >> 24) & 0xFF] ^
              t[3][(word >> 32) & 0xFF] ^ t[2][(word >> 40) & 0xFF] ^
              t[1][(word >> 48) & 0xFF] ^ t[0][(word >> 56) & 0xFF];
        data += 8;
        len -= 8;
    }
    while (len--) crc = t[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
    return ~crc;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2")))
uint32_t crc32c_hw_impl(uint32_t crc, const uint8_t* data, size_t len) {
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

uint32_t crc32c(const uint8_t* data, size_t len) {
#if defined(__x86_64__)
    if (__builtin_cpu_supports("sse4.2")) return crc32c_hw_impl(0, data, len);
#endif
    return crc32c_sw_impl(0, data, len);
}

// Legacy CRC.Value() form accepted on read (needle_read.go:73-80)
uint32_t crc_legacy_value(uint32_t crc) {
    uint32_t rotated = (crc >> 15) | (crc << 17);
    return rotated + 0xA282EAD8u;
}

// ---------------------------------------------------------------------------
// Big-endian helpers (all on-disk integers are big-endian)
// ---------------------------------------------------------------------------

inline void put_be32(uint8_t* p, uint32_t v) {
    p[0] = v >> 24; p[1] = v >> 16; p[2] = v >> 8; p[3] = v;
}
inline void put_be64(uint8_t* p, uint64_t v) {
    put_be32(p, (uint32_t)(v >> 32));
    put_be32(p + 4, (uint32_t)v);
}
inline uint32_t get_be32(const uint8_t* p) {
    return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
           ((uint32_t)p[2] << 8) | p[3];
}
inline uint64_t get_be64(const uint8_t* p) {
    return ((uint64_t)get_be32(p) << 32) | get_be32(p + 4);
}

// ---------------------------------------------------------------------------
// Needle format constants (storage/types.py <-> weed/storage/types)
// ---------------------------------------------------------------------------

constexpr int kHeaderSize = 16;     // cookie4 + id8 + size4
constexpr int kChecksumSize = 4;
constexpr int kTimestampSize = 8;
constexpr int kPaddingSize = 8;
constexpr int32_t kTombstone = -1;
constexpr int64_t kMaxVolumeSize = 32LL * 1024 * 1024 * 1024;
constexpr uint8_t kFlagHasLastModified = 0x08;
constexpr uint8_t kFlagHasTtl = 0x10;
constexpr int kLastModifiedBytes = 5;
constexpr int kTtlBytes = 2;  // count, unit (storage/ttl.py to_bytes)

// Cumulative request counters (exposed to Prometheus via
// svn_server_stats; native requests never enter Python, so the
// observability surface must be fed from here)
std::atomic<int64_t> g_stat_reads{0}, g_stat_ec_reads{0};
std::atomic<int64_t> g_stat_writes{0}, g_stat_deletes{0};
std::atomic<int64_t> g_stat_http_reads{0}, g_stat_fallbacks{0};
std::atomic<int64_t> g_stat_errors{0};

void count_reply(uint32_t status) {
    if (status == 307) g_stat_fallbacks.fetch_add(1);
    else if (status >= 400) g_stat_errors.fetch_add(1);
}

int padding_length(int64_t needle_size, int version) {
    int64_t base = kHeaderSize + needle_size + kChecksumSize;
    if (version == 3) base += kTimestampSize;
    return kPaddingSize - (int)(base % kPaddingSize);
}

int64_t get_actual_size(int64_t size, int version) {
    int64_t body = size + kChecksumSize + padding_length(size, version);
    if (version == 3) body += kTimestampSize;
    return kHeaderSize + body;
}

// ---------------------------------------------------------------------------
// Needle map: open addressing, linear probing, grow-only (deletes negate
// the stored size in place — compact_map.go Delete keeps the slot)
// ---------------------------------------------------------------------------

struct NeedleMapN {
    std::vector<uint64_t> keys;
    std::vector<uint64_t> offsets;   // actual byte offsets
    std::vector<int32_t> sizes;
    std::vector<uint8_t> used;
    size_t cap = 0, count = 0;
    // counters mirroring BaseNeedleMap (needle_map.py:53-110)
    int64_t file_count = 0, deleted_count = 0;
    int64_t content_bytes = 0, deleted_bytes = 0;
    uint64_t max_key = 0;
    mutable std::shared_mutex mu;

    NeedleMapN() { rehash(1024); }

    void rehash(size_t new_cap) {
        std::vector<uint64_t> ok = std::move(keys), oo = std::move(offsets);
        std::vector<int32_t> os = std::move(sizes);
        std::vector<uint8_t> ou = std::move(used);
        size_t old_cap = cap;
        cap = new_cap;
        keys.assign(cap, 0);
        offsets.assign(cap, 0);
        sizes.assign(cap, 0);
        used.assign(cap, 0);
        count = 0;
        for (size_t i = 0; i < old_cap; i++) {
            if (ou[i]) raw_insert(ok[i], oo[i], os[i]);
        }
    }

    size_t slot_for(uint64_t key) const {
        // splitmix64 finalizer as the hash
        uint64_t h = key + 0x9E3779B97F4A7C15ull;
        h = (h ^ (h >> 30)) * 0xBF58476D1CE4E5B9ull;
        h = (h ^ (h >> 27)) * 0x94D049BB133111EBull;
        h ^= h >> 31;
        size_t i = (size_t)(h & (cap - 1));
        while (used[i] && keys[i] != key) i = (i + 1) & (cap - 1);
        return i;
    }

    void raw_insert(uint64_t key, uint64_t off, int32_t size) {
        size_t i = slot_for(key);
        if (!used[i]) {
            used[i] = 1;
            keys[i] = key;
            count++;
        }
        offsets[i] = off;
        sizes[i] = size;
    }

    void maybe_grow() {
        if (count * 10 >= cap * 7) rehash(cap * 2);
    }

    // _apply (needle_map.py:92-110): replay/record one idx-entry worth of
    // state change, maintaining the counter set.
    void apply(uint64_t nid, uint64_t off, int32_t size) {
        if (nid > max_key) max_key = nid;
        if (off > 0 && size != kTombstone) {
            size_t i = slot_for(nid);
            if (used[i] && sizes[i] > 0) {
                deleted_count++;
                deleted_bytes += sizes[i];
            }
            maybe_grow();
            raw_insert(nid, off, size);
            file_count++;
            content_bytes += size;
        } else {
            size_t i = slot_for(nid);
            if (used[i] && sizes[i] > 0) {
                deleted_count++;
                deleted_bytes += sizes[i];
                sizes[i] = -sizes[i];  // keep offset, negate size
            }
        }
    }

    bool get(uint64_t nid, uint64_t* off, int32_t* size) const {
        size_t i = slot_for(nid);
        if (!used[i]) return false;
        *off = offsets[i];
        *size = sizes[i];
        return true;
    }
};

// ---------------------------------------------------------------------------
// Volume handle
// ---------------------------------------------------------------------------

struct NVolume {
    int dat_fd = -1, idx_fd = -1;
    int version = 3;
    std::mutex wmu;  // serializes .dat appends across Python + native paths
    NeedleMapN nm;
    std::atomic<uint64_t> last_append_ns{0};
    std::atomic<int64_t> last_modified_ts{0};
    std::atomic<bool> writable{false};   // native W/D allowed
    std::atomic<bool> read_only{false};
    std::atomic<bool> do_fsync{false};
    // TTL volumes: reads 404 expired needles (volume_read.go:27-35);
    // the daemon's vacuum still reclaims them.  ttl_raw is the volume
    // TTL's on-disk uint32 form ((count<<8)|unit, storage/ttl.py):
    // native writes stamp it into every needle so natively-written
    // needles on TTL volumes expire and vacuum like Python-written ones
    std::atomic<int64_t> ttl_sec{0};
    std::atomic<uint32_t> ttl_raw{0};
    // replicated volumes: native writes must fan out to this many other
    // locations (store_replicate.go:24-141); when the replica address
    // set is smaller, writes 307 to the Python handler instead
    std::atomic<int> extra_copies{0};

    // group commit for -fsync volumes (volume_write.go:233-306 /
    // _FsyncBatcher semantics): tickets issued under wmu; one leader
    // fsyncs for every ticket issued so far, the rest wait.  A failed
    // leader fsync fails EVERY ticket it covered (volume.py
    // _FsyncBatcher "_failed_upto = target" — an acknowledged write
    // must never ride a sync whose pages the kernel dropped).
    std::mutex fs_mu;
    std::condition_variable fs_cv;
    std::atomic<uint64_t> fs_seq{0};  // tickets issued (under wmu, but
                                      // read concurrently by leaders)
    uint64_t fs_done = 0;    // durable through this ticket
    uint64_t fs_failed = 0;  // failed-batch watermark
    bool fs_running = false;

    // Wait until `ticket` is covered by a group fsync; false when the
    // commit covering it failed (the write must be answered 500).
    bool fsync_ticket(uint64_t ticket) {
        std::unique_lock<std::mutex> lk(fs_mu);
        while (fs_done < ticket && fs_failed < ticket) {
            if (!fs_running) {
                fs_running = true;
                uint64_t target = fs_seq.load();
                lk.unlock();
                bool ok = fdatasync(dat_fd) == 0 && fdatasync(idx_fd) == 0;
                lk.lock();
                if (ok) {
                    if (target > fs_done) fs_done = target;
                } else if (target > fs_failed) {
                    fs_failed = target;
                }
                fs_running = false;
                fs_cv.notify_all();
            } else {
                fs_cv.wait(lk);
            }
        }
        return fs_done >= ticket;
    }

    ~NVolume() {
        if (dat_fd >= 0) close(dat_fd);
        if (idx_fd >= 0) close(idx_fd);
    }
};

using VolPtr = std::shared_ptr<NVolume>;

// GF(2^8)/0x11D multiplication table for degraded-read reconstruction
// (same construction as ec_native.cpp / ops/gf256.py).
struct GfMulTables {
    uint8_t mul[256][256];
    GfMulTables() {
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

const uint8_t (*gf_mul())[256] {
    static const GfMulTables t;
    return t.mul;
}

// Per-missing-shard recovery plan: reconstruct its bytes at any offset
// as XOR_j mul(coeffs[j], survivor_j bytes at the SAME offset) — the
// one-matmul survivor->missing row the daemon derives with
// rebuild_matrix (RS parity is columnwise, so spans align).
struct EcRecovery {
    uint8_t survivors[10];
    uint8_t coeffs[10];
};

// EC volume handle: sorted .ecx + local shard files.  Serves reads whose
// intervals all hit local shards; a missing shard's span reconstructs
// on the fly from 10 local survivors when the daemon pushed a recovery
// plan (native degraded reads — recoverOneRemoteEcShardInterval,
// store_ec.go:328-382, minus the remote fetches); anything else answers
// 307 and the client falls back to the HTTP ladder (local -> remote ->
// reconstruct, store_ec.go:125-163).  Writes/deletes stay in Python.
struct NEcVolume {
    int ecx_fd = -1;
    std::atomic<int64_t> ecx_entries{0};
    int version = 3;
    int64_t large_block = 0, small_block = 0;
    std::atomic<int64_t> shard_size{0};  // any local shard's file size
    // atomic slots: server threads read them lock-free mid-request.
    // Replaced/removed fds are RETIRED, not closed — an in-flight pread
    // must never hit EBADF or a reused descriptor; the handful of fds a
    // remount churn leaves open are released in the destructor.
    std::atomic<int> shard_fds[14];
    std::mutex retired_mu;
    std::vector<int> retired;
    mutable std::shared_mutex recovery_mu;
    std::unique_ptr<EcRecovery> recovery[14];
    NEcVolume() {
        for (int i = 0; i < 14; i++) shard_fds[i].store(-1);
    }
    // copy of shard sid's recovery plan, or false when none is set
    bool get_recovery(int sid, EcRecovery* out) const {
        std::shared_lock<std::shared_mutex> lk(recovery_mu);
        if (!recovery[sid]) return false;
        *out = *recovery[sid];
        return true;
    }
    void retire(int fd) {
        if (fd < 0) return;
        std::lock_guard<std::mutex> lk(retired_mu);
        retired.push_back(fd);
    }
    ~NEcVolume() {
        if (ecx_fd >= 0) close(ecx_fd);
        for (int i = 0; i < 14; i++) {
            int fd = shard_fds[i].load();
            if (fd >= 0) close(fd);
        }
        for (int fd : retired) close(fd);
    }
};

using EcPtr = std::shared_ptr<NEcVolume>;

std::shared_mutex g_reg_mu;
std::unordered_map<int64_t, VolPtr> g_handles;     // handle -> volume
std::unordered_map<uint32_t, int64_t> g_serving;   // vid -> handle
std::unordered_map<int64_t, EcPtr> g_ec_handles;   // handle -> EC volume
std::unordered_map<uint32_t, int64_t> g_ec_serving;  // vid -> EC handle
std::atomic<int64_t> g_next_handle{1};

// JWT keys for the fast-path port; set before svn_server_start (the
// Python daemon configures them from security.toml at startup).
std::mutex g_jwt_mu;
std::string g_jwt_write_key, g_jwt_read_key;
int g_jwt_expire_s = 10;

// Signature-verification memo: a count>N assign shares ONE token across
// all N chunk writes (plus every replica forward re-verifies it), so
// the same (key, token) pair is HMAC'd over and over on the hottest
// write path.  Only successful signature checks are cached and `exp` is
// re-evaluated on every lookup, so a hit can never outlive the token.
// Cleared whenever a signing key changes.
struct JwtVerified {
    std::string fid;
    int64_t exp = 0;
    bool has_exp = false;
};
std::mutex g_jwt_cache_mu;
std::unordered_map<std::string, JwtVerified> g_jwt_cache;
constexpr size_t kJwtCacheMax = 4096;

void jwt_cache_clear() {
    std::lock_guard<std::mutex> lk(g_jwt_cache_mu);
    g_jwt_cache.clear();
}

// Replica fan-out registry: vid -> peer fast-path addresses.
std::shared_mutex g_replica_mu;
std::unordered_map<uint32_t, std::vector<std::string>> g_replicas;

VolPtr handle_vol(int64_t h) {
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_handles.find(h);
    return it == g_handles.end() ? nullptr : it->second;
}

VolPtr serving_vol(uint32_t vid) {
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_serving.find(vid);
    if (it == g_serving.end()) return nullptr;
    auto hit = g_handles.find(it->second);
    return hit == g_handles.end() ? nullptr : hit->second;
}

EcPtr serving_ec(uint32_t vid) {
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_ec_serving.find(vid);
    if (it == g_ec_serving.end()) return nullptr;
    auto hit = g_ec_handles.find(it->second);
    return hit == g_ec_handles.end() ? nullptr : hit->second;
}

bool append_idx_entry(NVolume* v, uint64_t nid, uint64_t off, int32_t size) {
    uint8_t e[16];
    put_be64(e, nid);
    put_be32(e + 8, (uint32_t)(off / kPaddingSize));  // stored ÷8 (offset.go)
    put_be32(e + 12, (uint32_t)size);
    return write(v->idx_fd, e, 16) == 16;  // O_APPEND: atomic
}

// pread exactly n bytes; false on short read / error
bool pread_full(int fd, uint8_t* buf, size_t n, int64_t off) {
    size_t got = 0;
    while (got < n) {
        ssize_t r = pread(fd, buf + got, n - got, off + got);
        if (r <= 0) return false;
        got += (size_t)r;
    }
    return true;
}

bool pwrite_full(int fd, const uint8_t* buf, size_t n, int64_t off) {
    size_t put = 0;
    while (put < n) {
        ssize_t r = pwrite(fd, buf + put, n - put, off + put);
        if (r < 0) return false;
        put += (size_t)r;
    }
    return true;
}

uint64_t now_unix_ns() {
    return (uint64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::system_clock::now().time_since_epoch())
        .count();
}

// Parse a needle record's data section (needle_read.go:98-177).  Returns
// false on structural error.  `data_off`/`data_len` locate the payload
// inside `blob`; `cookie` and CRC are verified by the caller.
bool parse_needle_data(const uint8_t* blob, int64_t blob_len, int32_t size,
                       int version, int64_t* data_off, int64_t* data_len) {
    if (version == 1) {
        if (kHeaderSize + size > blob_len) return false;
        *data_off = kHeaderSize;
        *data_len = size;
        return true;
    }
    if (size == 0) {
        *data_off = kHeaderSize;
        *data_len = 0;
        return true;
    }
    if (kHeaderSize + 4 > blob_len) return false;
    uint32_t dsize = get_be32(blob + kHeaderSize);
    if (kHeaderSize + 4 + (int64_t)dsize > blob_len) return false;
    *data_off = kHeaderSize + 4;
    *data_len = dsize;
    return true;
}

// Walk the needle body's optional fields to the 5-byte lastModified
// (needle layout: Data, Flags, [Name], [Mime], [LastModified], ... —
// needle_read.go:114-177).  0 when absent/unparseable.
int64_t needle_last_modified(const uint8_t* b, int64_t blob_len,
                             int32_t size, int version) {
    if (version == 1 || size <= 0) return 0;
    if (kHeaderSize + 4 > blob_len) return 0;
    uint32_t dsize = get_be32(b + kHeaderSize);
    int64_t p = kHeaderSize + 4 + (int64_t)dsize;
    int64_t end = std::min<int64_t>(kHeaderSize + size, blob_len);
    if (p >= end) return 0;
    uint8_t flags = b[p++];
    if (flags & 0x02) {  // HAS_NAME
        if (p >= end) return 0;
        p += 1 + b[p];
    }
    if (flags & 0x04) {  // HAS_MIME
        if (p >= end) return 0;
        p += 1 + b[p];
    }
    if (!(flags & kFlagHasLastModified)) return 0;
    if (p + kLastModifiedBytes > end) return 0;
    int64_t v = 0;
    for (int i = 0; i < kLastModifiedBytes; i++) v = (v << 8) | b[p + i];
    return v;
}

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// Registration / needle-map API (ctypes surface)
// ---------------------------------------------------------------------------

// Open the volume's .dat/.idx, replay the idx into the in-RAM map, and
// return a handle (>0) or -errno.
int64_t svn_register(const char* dat_path, const char* idx_path, int version,
                     int writable, int read_only, int do_fsync) {
    auto v = std::make_shared<NVolume>();
    v->version = version;
    v->writable.store(writable != 0);
    v->read_only.store(read_only != 0);
    v->do_fsync.store(do_fsync != 0);
    v->dat_fd = open(dat_path, O_RDWR);
    if (v->dat_fd < 0) return -errno;
    v->idx_fd = open(idx_path, O_RDWR | O_CREAT | O_APPEND, 0644);
    if (v->idx_fd < 0) return -errno;
    // replay existing idx entries (needle_map_memory.go doLoading)
    struct stat st;
    if (fstat(v->idx_fd, &st) == 0 && st.st_size >= 16) {
        int64_t n_entries = st.st_size / 16;
        std::vector<uint8_t> buf(1 << 20);
        int64_t pos = 0;
        while (pos < n_entries * 16) {
            int64_t chunk =
                std::min<int64_t>((int64_t)buf.size(), n_entries * 16 - pos);
            chunk -= chunk % 16;
            if (!pread_full(v->idx_fd, buf.data(), (size_t)chunk, pos))
                break;
            for (int64_t e = 0; e < chunk; e += 16) {
                uint64_t nid = get_be64(&buf[e]);
                uint64_t off =
                    (uint64_t)get_be32(&buf[e + 8]) * kPaddingSize;
                int32_t size = (int32_t)get_be32(&buf[e + 12]);
                v->nm.apply(nid, off, size);
            }
            pos += chunk;
        }
    }
    int64_t h = g_next_handle.fetch_add(1);
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    g_handles[h] = std::move(v);
    return h;
}

int svn_unregister(int64_t handle) {
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    for (auto it = g_serving.begin(); it != g_serving.end();) {
        if (it->second == handle) it = g_serving.erase(it);
        else ++it;
    }
    return g_handles.erase(handle) ? 0 : -1;
}

int svn_set_flags(int64_t handle, int writable, int read_only) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    if (writable >= 0) v->writable.store(writable != 0);
    if (read_only >= 0) v->read_only.store(read_only != 0);
    return 0;
}

// TTL volumes: native reads 404 needles older than ttl_sec (0 = none);
// native writes append ttl_raw ((count<<8)|unit) to each needle.
int svn_set_ttl(int64_t handle, int64_t ttl_sec, uint32_t ttl_raw) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    v->ttl_sec.store(ttl_sec);
    v->ttl_raw.store(ttl_raw);
    return 0;
}

// Replicated volumes: native writes fan out to `extra_copies` other
// locations (or 307 when the replica set is not configured).
int svn_set_replication(int64_t handle, int extra_copies) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    v->extra_copies.store(extra_copies);
    return 0;
}

// Replace vid's peer fast-path addresses ("host:port,host:port"; empty
// or NULL clears).  The daemon refreshes these from master lookups.
int svn_set_replicas(uint32_t vid, const char* csv) {
    std::vector<std::string> addrs;
    if (csv) {
        const char* p = csv;
        while (*p) {
            const char* comma = strchr(p, ',');
            size_t n = comma ? (size_t)(comma - p) : strlen(p);
            if (n) addrs.emplace_back(p, n);
            p += n + (comma ? 1 : 0);
        }
    }
    std::unique_lock<std::shared_mutex> lk(g_replica_mu);
    if (addrs.empty()) g_replicas.erase(vid);
    else g_replicas[vid] = std::move(addrs);
    return 0;
}

// HS256 signing keys for the fast-path port (security.toml jwt.signing
// / jwt.signing.read — guard.go:18-50).  Empty string disables a key;
// NULL leaves that key untouched.  The keys are ENGINE-global and the
// engine is shared by every in-process daemon, so each owner (master
// guard, volume guard) must only ever set/clear ITS key — a master
// shutting down must not also clear the volume server's read key.
int svn_server_set_jwt(const char* write_key, const char* read_key,
                       int expire_s) {
    {
        std::lock_guard<std::mutex> lk(g_jwt_mu);
        if (write_key) g_jwt_write_key = write_key;
        if (read_key) g_jwt_read_key = read_key;
        if (expire_s > 0) g_jwt_expire_s = expire_s;
    }
    // verified signatures are key-dependent: a rotated/cleared key must
    // not keep honoring tokens minted under the old one
    if (write_key || read_key) jwt_cache_clear();
    return 0;
}

// Bind/unbind a volume id to a handle for the TCP server
int svn_serve(uint32_t vid, int64_t handle) {
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    if (handle <= 0) {
        g_serving.erase(vid);
        return 0;
    }
    if (!g_handles.count(handle)) return -1;
    g_serving[vid] = handle;
    return 0;
}

int svn_nm_put(int64_t handle, uint64_t nid, uint64_t off, int64_t size) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    std::unique_lock<std::shared_mutex> lk(v->nm.mu);
    v->nm.apply(nid, off, (int32_t)size);
    return append_idx_entry(v.get(), nid, off, (int32_t)size) ? 0 : -errno;
}

int svn_nm_delete(int64_t handle, uint64_t nid, uint64_t tomb_off) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    std::unique_lock<std::shared_mutex> lk(v->nm.mu);
    // idx log FIRST: an ENOSPC/EIO append must fail the request before
    // the in-RAM map records a state the log never will (the Python
    // caller raises on a negative return)
    if (!append_idx_entry(v.get(), nid, tomb_off, kTombstone))
        return -(errno ? errno : EIO);
    v->nm.apply(nid, 0, kTombstone);
    return 0;
}

int svn_nm_set_memory(int64_t handle, uint64_t nid, uint64_t off,
                      int64_t size) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    std::unique_lock<std::shared_mutex> lk(v->nm.mu);
    v->nm.apply(nid, off, (int32_t)size);
    return 0;
}

// -> 1 found (fills off/size; negative size = deleted), 0 absent, <0 error
int svn_nm_get(int64_t handle, uint64_t nid, uint64_t* off, int64_t* size) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    std::shared_lock<std::shared_mutex> lk(v->nm.mu);
    uint64_t o;
    int32_t s;
    if (!v->nm.get(nid, &o, &s)) return 0;
    *off = o;
    *size = s;
    return 1;
}

// out[0..6] = file_count, deleted_count, content_bytes, deleted_bytes,
//             max_key, live_slot_count, last_append_ns
int svn_nm_stats(int64_t handle, int64_t* out) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    std::shared_lock<std::shared_mutex> lk(v->nm.mu);
    out[0] = v->nm.file_count;
    out[1] = v->nm.deleted_count;
    out[2] = v->nm.content_bytes;
    out[3] = v->nm.deleted_bytes;
    out[4] = (int64_t)v->nm.max_key;
    out[5] = (int64_t)v->nm.count;
    out[6] = (int64_t)v->last_append_ns.load();
    return 0;
}

// Fill `out` with (nid, offset, size) int64 triples in ascending nid order.
// Returns the entry count, -needed when cap_entries is too small, or
// INT64_MIN for an unknown handle (distinguishable from any capacity ask).
int64_t svn_nm_visit(int64_t handle, int64_t* out, int64_t cap_entries) {
    auto v = handle_vol(handle);
    if (!v) return INT64_MIN;
    std::shared_lock<std::shared_mutex> lk(v->nm.mu);
    int64_t n = (int64_t)v->nm.count;
    if (n > cap_entries) return -n;
    std::vector<size_t> idx;
    idx.reserve((size_t)n);
    for (size_t i = 0; i < v->nm.cap; i++)
        if (v->nm.used[i]) idx.push_back(i);
    std::sort(idx.begin(), idx.end(), [&](size_t a, size_t b) {
        return v->nm.keys[a] < v->nm.keys[b];
    });
    int64_t w = 0;
    for (size_t i : idx) {
        out[w * 3] = (int64_t)v->nm.keys[i];
        out[w * 3 + 1] = (int64_t)v->nm.offsets[i];
        out[w * 3 + 2] = v->nm.sizes[i];
        w++;
    }
    return n;
}

// Append a record the caller built and stamped to the .dat and point the
// map at it, in one call.  The append mutex is shared with the native
// write path, so Python-side writes and native-port writes never
// interleave.  Under it, at the point where the offset is allocated:
// the caller decided (dedup, cookie rule, a delete's freed size) against
// the map entry (`expect_off`, `expect_size`), 0 for no entry; if the map
// says otherwise now, nothing is written and -EEXIST sends the caller to
// decide again; a record that would end past `limit` is refused with
// -EFBIG.  Then, under the map
// lock: apply + log the entry only when it is newer than the current
// mapping (the volume_write.go:160-165 "nv.Offset < offset" guard,
// evaluated atomically so a racing native-port write to the same id
// cannot be clobbered by a stale Python-side put); for size ==
// kTombstone, svn_nm_delete's entry.  Returns the landing offset or
// -errno: an idx append that fails (ENOSPC/EIO) must fail the request
// before it is acknowledged, not vanish on restart.
int64_t svn_append_put(int64_t handle, const uint8_t* blob, int64_t len,
                       uint64_t nid, int64_t size, uint64_t expect_off,
                       int64_t expect_size, int64_t limit) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    int64_t end;
    {
        std::lock_guard<std::mutex> lk(v->wmu);
        {
            std::shared_lock<std::shared_mutex> mlk(v->nm.mu);
            uint64_t cur_off = 0;
            int32_t cur_size = 0;
            if (!v->nm.get(nid, &cur_off, &cur_size)) cur_off = 0;
            if (cur_off != expect_off ||
                (cur_off && cur_size != expect_size))
                return -EEXIST;
        }
        end = lseek(v->dat_fd, 0, SEEK_END);
        if (end < 0) return -errno;
        if (end + len > limit) return -EFBIG;
        if (!pwrite_full(v->dat_fd, blob, (size_t)len, end)) return -errno;
    }
    std::unique_lock<std::shared_mutex> lk(v->nm.mu);
    if (size == kTombstone) {
        if (!append_idx_entry(v.get(), nid, (uint64_t)end, kTombstone))
            return -(errno ? errno : EIO);
        v->nm.apply(nid, 0, kTombstone);
        return end;
    }
    uint64_t cur_off;
    int32_t cur_size;
    if (v->nm.get(nid, &cur_off, &cur_size) && cur_off >= (uint64_t)end)
        return end;
    if (!append_idx_entry(v.get(), nid, (uint64_t)end, (int32_t)size))
        return -(errno ? errno : EIO);
    v->nm.apply(nid, (uint64_t)end, (int32_t)size);
    return end;
}

int64_t svn_size(int64_t handle) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    struct stat st;
    if (fstat(v->dat_fd, &st) != 0) return -errno;
    return st.st_size;
}

int svn_sync(int64_t handle) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    if (fdatasync(v->idx_fd) != 0) return -errno;
    if (fdatasync(v->dat_fd) != 0) return -errno;
    return 0;
}

int svn_touch(int64_t handle, uint64_t append_ns, int64_t modified_ts) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    if (append_ns > v->last_append_ns.load())
        v->last_append_ns.store(append_ns);
    if (modified_ts > v->last_modified_ts.load())
        v->last_modified_ts.store(modified_ts);
    return 0;
}

int64_t svn_last_modified(int64_t handle) {
    auto v = handle_vol(handle);
    return v ? v->last_modified_ts.load() : -1;
}

// Disable native writes and drain any in-flight append (vacuum commit
// barrier: after this returns, no native write can touch the old files)
int svn_quiesce(int64_t handle) {
    auto v = handle_vol(handle);
    if (!v) return -1;
    v->writable.store(false);
    std::lock_guard<std::mutex> lk(v->wmu);
    return 0;
}

// ---------------------------------------------------------------------------
// EC volume API
// ---------------------------------------------------------------------------

int64_t svn_ec_register(const char* ecx_path, int version,
                        int64_t large_block, int64_t small_block) {
    auto ev = std::make_shared<NEcVolume>();
    ev->version = version;
    ev->large_block = large_block;
    ev->small_block = small_block;
    ev->ecx_fd = open(ecx_path, O_RDONLY);
    if (ev->ecx_fd < 0) return -errno;
    struct stat st;
    if (fstat(ev->ecx_fd, &st) != 0) return -errno;
    ev->ecx_entries.store(st.st_size / 16);
    int64_t h = g_next_handle.fetch_add(1);
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    g_ec_handles[h] = std::move(ev);
    return h;
}

int svn_ec_add_shard(int64_t handle, int shard_id, const char* path) {
    if (shard_id < 0 || shard_id >= 14) return -1;
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_ec_handles.find(handle);
    if (it == g_ec_handles.end()) return -1;
    auto& ev = it->second;
    int fd = open(path, O_RDONLY);
    if (fd < 0) return -errno;
    struct stat st;
    if (fstat(fd, &st) != 0) {
        close(fd);
        return -errno;
    }
    ev->retire(ev->shard_fds[shard_id].exchange(fd));
    ev->shard_size.store(st.st_size);
    return 0;
}

int svn_ec_remove_shard(int64_t handle, int shard_id) {
    if (shard_id < 0 || shard_id >= 14) return -1;
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_ec_handles.find(handle);
    if (it == g_ec_handles.end()) return -1;
    auto& ev = it->second;
    ev->retire(ev->shard_fds[shard_id].exchange(-1));
    return 0;
}

int svn_ec_serve(uint32_t vid, int64_t handle) {
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    if (handle <= 0) {
        g_ec_serving.erase(vid);
        return 0;
    }
    if (!g_ec_handles.count(handle)) return -1;
    g_ec_serving[vid] = handle;
    return 0;
}

int svn_ec_unregister(int64_t handle) {
    std::unique_lock<std::shared_mutex> lk(g_reg_mu);
    for (auto it = g_ec_serving.begin(); it != g_ec_serving.end();) {
        if (it->second == handle) it = g_ec_serving.erase(it);
        else ++it;
    }
    return g_ec_handles.erase(handle) ? 0 : -1;
}

// Install (n=10) or clear (n=0) shard_id's degraded-read recovery plan:
// `survivors` are 10 shard ids whose same-offset bytes, combined with
// `coeffs` under GF(2^8), reproduce shard_id's bytes.  The daemon
// derives the row with rebuild_matrix at shard-sync time.
int svn_ec_set_recovery(int64_t handle, int shard_id,
                        const uint8_t* survivors, const uint8_t* coeffs,
                        int n) {
    std::shared_lock<std::shared_mutex> rlk(g_reg_mu);
    auto it = g_ec_handles.find(handle);
    if (it == g_ec_handles.end()) return -1;
    auto ev = it->second;
    rlk.unlock();
    if (shard_id < 0 || shard_id >= 14) return -1;
    std::unique_lock<std::shared_mutex> lk(ev->recovery_mu);
    if (n != 10) {
        ev->recovery[shard_id].reset();
        return 0;
    }
    for (int j = 0; j < 10; j++)
        if (survivors[j] >= 14) return -1;  // would index OOB on read
    auto rec = std::make_unique<EcRecovery>();
    memcpy(rec->survivors, survivors, 10);
    memcpy(rec->coeffs, coeffs, 10);
    ev->recovery[shard_id] = std::move(rec);
    return 0;
}

// Refresh the cached .ecx entry count (the file grows only on rebuild;
// deletes rewrite size fields in place, which preads observe directly)
int svn_ec_refresh(int64_t handle) {
    std::shared_lock<std::shared_mutex> lk(g_reg_mu);
    auto it = g_ec_handles.find(handle);
    if (it == g_ec_handles.end()) return -1;
    struct stat st;
    if (fstat(it->second->ecx_fd, &st) != 0) return -errno;
    it->second->ecx_entries.store(st.st_size / 16);
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Request handling shared by the TCP server
// ---------------------------------------------------------------------------

namespace {

struct Reply {
    uint32_t status;  // 0 = OK (payload = data / JSON); else error code
    std::string payload;
};

// Parse "vid,<idhex><cookie8hex>[_delta]" (storage/types.py:91-111)
bool parse_fid(const std::string& fid, uint32_t* vid, uint64_t* nid,
               uint32_t* cookie) {
    size_t comma = fid.find(',');
    if (comma == std::string::npos) return false;
    errno = 0;
    char* endp = nullptr;
    unsigned long vv = strtoul(fid.c_str(), &endp, 10);
    if (errno || endp != fid.c_str() + comma) return false;
    std::string key = fid.substr(comma + 1);
    uint64_t delta = 0;
    size_t us = key.rfind('_');
    if (us != std::string::npos) {
        delta = strtoull(key.c_str() + us + 1, nullptr, 10);
        key = key.substr(0, us);
    }
    if (key.size() <= 8 || key.size() > 24) return false;
    std::string id_hex = key.substr(0, key.size() - 8);
    std::string ck_hex = key.substr(key.size() - 8);
    errno = 0;
    uint64_t id = strtoull(id_hex.c_str(), &endp, 16);
    if (errno || *endp) return false;
    uint32_t ck = (uint32_t)strtoul(ck_hex.c_str(), &endp, 16);
    if (*endp) return false;
    *vid = (uint32_t)vv;
    *nid = id + delta;
    *cookie = ck;
    return true;
}

// gunzip a stored-compressed needle payload (the HTTP handler without
// Accept-Encoding: gzip decompresses; the fast path must agree —
// volume_server_handlers_read.go:180-199 semantics)
bool gunzip(const std::string& in, std::string* out) {
    z_stream zs{};
    if (inflateInit2(&zs, 15 + 16) != Z_OK) return false;  // gzip wrapper
    out->clear();
    out->reserve(in.size() * 3);
    char buf[1 << 16];
    zs.next_in = (Bytef*)in.data();
    zs.avail_in = (uInt)in.size();
    // loop on Z_OK, not on remaining input: inflate may still hold
    // window output after the last input byte (long back-references);
    // a truncated/non-progressing stream surfaces as Z_BUF_ERROR
    int rc = Z_OK;
    while (rc == Z_OK) {
        zs.next_out = (Bytef*)buf;
        zs.avail_out = sizeof(buf);
        rc = inflate(&zs, Z_NO_FLUSH);
        if (rc != Z_OK && rc != Z_STREAM_END) {
            inflateEnd(&zs);
            return false;
        }
        out->append(buf, sizeof(buf) - zs.avail_out);
    }
    inflateEnd(&zs);
    return rc == Z_STREAM_END;
}

// ---------------------------------------------------------------------------
// SHA-256 / HMAC-SHA256 / base64url — self-contained (no OpenSSL), for
// HS256 JWT verification and minting on the fast-path port.  Semantics
// mirror security/jwt_auth.py (itself weed/security/jwt.go + guard.go:
// fid-scoped claims, exp checked, HS256 only — and because verification
// recomputes HMAC-SHA256 unconditionally, alg-confusion tokens like
// "alg":"none" can never pass).
// ---------------------------------------------------------------------------

struct Sha256 {
    uint32_t h[8];
    uint64_t len = 0;
    uint8_t buf[64];
    size_t fill = 0;
    Sha256() {
        static const uint32_t init[8] = {
            0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
            0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};
        memcpy(h, init, sizeof(h));
    }
    static uint32_t rotr(uint32_t x, int n) {
        return (x >> n) | (x << (32 - n));
    }
    void block(const uint8_t* p) {
        static const uint32_t k[64] = {
            0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b,
            0x59f111f1, 0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01,
            0x243185be, 0x550c7dc3, 0x72be5d74, 0x80deb1fe, 0x9bdc06a7,
            0xc19bf174, 0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
            0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da, 0x983e5152,
            0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
            0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc,
            0x53380d13, 0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
            0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3, 0xd192e819,
            0xd6990624, 0xf40e3585, 0x106aa070, 0x19a4c116, 0x1e376c08,
            0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f,
            0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
            0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};
        uint32_t w[64];
        for (int i = 0; i < 16; i++)
            w[i] = ((uint32_t)p[4 * i] << 24) | ((uint32_t)p[4 * i + 1] << 16) |
                   ((uint32_t)p[4 * i + 2] << 8) | p[4 * i + 3];
        for (int i = 16; i < 64; i++) {
            uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^
                          (w[i - 15] >> 3);
            uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^
                          (w[i - 2] >> 10);
            w[i] = w[i - 16] + s0 + w[i - 7] + s1;
        }
        uint32_t a = h[0], b = h[1], c = h[2], d = h[3], e = h[4],
                 f = h[5], g = h[6], hh = h[7];
        for (int i = 0; i < 64; i++) {
            uint32_t s1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
            uint32_t ch = (e & f) ^ (~e & g);
            uint32_t t1 = hh + s1 + ch + k[i] + w[i];
            uint32_t s0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
            uint32_t mj = (a & b) ^ (a & c) ^ (b & c);
            uint32_t t2 = s0 + mj;
            hh = g; g = f; f = e; e = d + t1;
            d = c; c = b; b = a; a = t1 + t2;
        }
        h[0] += a; h[1] += b; h[2] += c; h[3] += d;
        h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
    }
    void update(const void* data, size_t n) {
        const uint8_t* p = (const uint8_t*)data;
        len += n;
        if (fill) {
            size_t take = std::min(n, 64 - fill);
            memcpy(buf + fill, p, take);
            fill += take;
            p += take;
            n -= take;
            if (fill == 64) {
                block(buf);
                fill = 0;
            }
        }
        while (n >= 64) {
            block(p);
            p += 64;
            n -= 64;
        }
        if (n) {
            memcpy(buf, p, n);
            fill = n;
        }
    }
    void final(uint8_t out[32]) {
        uint64_t bits = len * 8;
        uint8_t pad = 0x80;
        update(&pad, 1);
        uint8_t zero = 0;
        while (fill != 56) update(&zero, 1);
        uint8_t lenb[8];
        for (int i = 0; i < 8; i++)
            lenb[i] = (uint8_t)(bits >> (8 * (7 - i)));
        update(lenb, 8);
        for (int i = 0; i < 8; i++) {
            out[4 * i] = (uint8_t)(h[i] >> 24);
            out[4 * i + 1] = (uint8_t)(h[i] >> 16);
            out[4 * i + 2] = (uint8_t)(h[i] >> 8);
            out[4 * i + 3] = (uint8_t)h[i];
        }
    }
};

void hmac_sha256(const std::string& key, const std::string& msg,
                 uint8_t out[32]) {
    uint8_t k[64] = {0};
    if (key.size() > 64) {
        Sha256 kh;
        kh.update(key.data(), key.size());
        kh.final(k);
    } else {
        memcpy(k, key.data(), key.size());
    }
    uint8_t ipad[64], opad[64];
    for (int i = 0; i < 64; i++) {
        ipad[i] = k[i] ^ 0x36;
        opad[i] = k[i] ^ 0x5c;
    }
    uint8_t inner[32];
    Sha256 si;
    si.update(ipad, 64);
    si.update(msg.data(), msg.size());
    si.final(inner);
    Sha256 so;
    so.update(opad, 64);
    so.update(inner, 32);
    so.final(out);
}

const char* kB64Url =
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_";

std::string b64url_encode(const uint8_t* data, size_t n) {
    std::string out;
    out.reserve((n + 2) / 3 * 4);
    for (size_t i = 0; i < n; i += 3) {
        uint32_t v = (uint32_t)data[i] << 16;
        if (i + 1 < n) v |= (uint32_t)data[i + 1] << 8;
        if (i + 2 < n) v |= data[i + 2];
        out += kB64Url[(v >> 18) & 63];
        out += kB64Url[(v >> 12) & 63];
        if (i + 1 < n) out += kB64Url[(v >> 6) & 63];
        if (i + 2 < n) out += kB64Url[v & 63];
    }
    return out;  // unpadded, like jwt_auth.py _b64url
}

bool b64url_decode(const std::string& in, std::string* out) {
    static int8_t rev[256];
    static bool init = false;
    if (!init) {
        memset(rev, -1, sizeof(rev));
        for (int i = 0; i < 64; i++) rev[(uint8_t)kB64Url[i]] = (int8_t)i;
        rev[(uint8_t)'+'] = 62;  // accept standard alphabet too
        rev[(uint8_t)'/'] = 63;
        init = true;
    }
    out->clear();
    uint32_t acc = 0;
    int bits = 0;
    for (char c : in) {
        if (c == '=') break;
        int8_t v = rev[(uint8_t)c];
        if (v < 0) return false;
        acc = (acc << 6) | (uint32_t)v;
        bits += 6;
        if (bits >= 8) {
            bits -= 8;
            out->push_back((char)((acc >> bits) & 0xFF));
        }
    }
    return true;
}

std::string jwt_key(bool write) {
    std::lock_guard<std::mutex> lk(g_jwt_mu);
    return write ? g_jwt_write_key : g_jwt_read_key;
}

// Extract a string claim ("fid") from a JSON payload minted by the
// framework/reference (flat object, no escapes inside fids).
bool json_str_claim(const std::string& json, const char* name,
                    std::string* out) {
    std::string pat = std::string("\"") + name + "\":";
    size_t p = json.find(pat);
    if (p == std::string::npos) return false;
    p += pat.size();
    while (p < json.size() && json[p] == ' ') p++;
    if (p >= json.size() || json[p] != '"') return false;
    size_t e = json.find('"', p + 1);
    if (e == std::string::npos) return false;
    *out = json.substr(p + 1, e - p - 1);
    return true;
}

bool json_num_claim(const std::string& json, const char* name,
                    int64_t* out) {
    std::string pat = std::string("\"") + name + "\":";
    size_t p = json.find(pat);
    if (p == std::string::npos) return false;
    p += pat.size();
    while (p < json.size() && json[p] == ' ') p++;
    errno = 0;
    char* endp = nullptr;
    long long v = strtoll(json.c_str() + p, &endp, 10);
    if (errno || endp == json.c_str() + p) return false;
    *out = (int64_t)v;
    return true;
}

// Verify an HS256 write/read token scoped to `fid` (guard.go:18-50 /
// jwt_auth.py decode_jwt + the fid-claim checks).  Write semantics
// accept the base fid of a count>1 assign ("fid_3" matches claim "fid",
// the file-id delta convention) and volume-level tokens ("3," claims
// authorize any fid in volume 3) — jwt_auth.py verify_write:134-140;
// read tokens compare exactly (verify_read:151).
bool jwt_verify(const std::string& key, const std::string& token,
                const std::string& fid, bool write_semantics) {
    // cache the expensive part (HMAC + base64 + claim parse) keyed by
    // (key, token); the per-fid claim check and exp re-check below stay
    // per call
    std::string cache_key;
    cache_key.reserve(key.size() + 1 + token.size());
    cache_key.append(key).push_back('\0');
    cache_key.append(token);
    JwtVerified entry;
    bool cached = false;
    {
        std::lock_guard<std::mutex> lk(g_jwt_cache_mu);
        auto it = g_jwt_cache.find(cache_key);
        if (it != g_jwt_cache.end()) {
            entry = it->second;
            cached = true;
        }
    }
    if (!cached) {
        size_t d1 = token.find('.');
        if (d1 == std::string::npos) return false;
        size_t d2 = token.find('.', d1 + 1);
        if (d2 == std::string::npos) return false;
        uint8_t mac[32];
        hmac_sha256(key, token.substr(0, d2), mac);
        std::string sig;
        if (!b64url_decode(token.substr(d2 + 1), &sig) || sig.size() != 32)
            return false;
        // constant-time compare
        uint8_t diff = 0;
        for (int i = 0; i < 32; i++) diff |= mac[i] ^ (uint8_t)sig[i];
        if (diff) return false;
        std::string payload;
        if (!b64url_decode(token.substr(d1 + 1, d2 - d1 - 1), &payload))
            return false;
        int64_t exp;
        entry.has_exp = json_num_claim(payload, "exp", &exp);
        if (entry.has_exp) entry.exp = exp;
        if (!json_str_claim(payload, "fid", &entry.fid)) return false;
        std::lock_guard<std::mutex> lk(g_jwt_cache_mu);
        if (g_jwt_cache.size() >= kJwtCacheMax) g_jwt_cache.clear();
        g_jwt_cache.emplace(std::move(cache_key), entry);
    }
    if (entry.has_exp) {
        int64_t now = (int64_t)(now_unix_ns() / 1000000000ull);
        if (now > entry.exp) return false;
    }
    const std::string& claim_fid = entry.fid;
    if (!write_semantics) return claim_fid == fid;
    if (claim_fid == fid.substr(0, fid.find('_'))) return true;
    return !claim_fid.empty() && claim_fid.back() == ',' &&
           fid.rfind(claim_fid, 0) == 0;
}

// Mint a write token for an assign reply (jwt.go GenJwtForVolumeServer).
std::string jwt_mint(const std::string& key, const std::string& fid,
                     int expire_s) {
    static const char* header_b64 =
        "eyJhbGciOiJIUzI1NiIsInR5cCI6IkpXVCJ9";  // {"alg":"HS256","typ":"JWT"}
    std::string claims = "{\"fid\":\"" + fid + "\"";
    if (expire_s > 0) {
        int64_t now = (int64_t)(now_unix_ns() / 1000000000ull);
        claims += ",\"exp\":" + std::to_string(now + expire_s);
    }
    claims += "}";
    std::string signing = std::string(header_b64) + "." +
                          b64url_encode((const uint8_t*)claims.data(),
                                        claims.size());
    uint8_t mac[32];
    hmac_sha256(key, signing, mac);
    return signing + "." + b64url_encode(mac, 32);
}

// Verify + extract the payload from a full needle record blob: size and
// cookie checks, CRC over data, store-side-gzip decompression
// (needle_read.go ReadBytes:52-95 + the HTTP handler's encoding rules)
Reply finish_needle_read(const std::string& blob, int32_t size, int version,
                         uint32_t cookie) {
    const uint8_t* b = (const uint8_t*)blob.data();
    int64_t actual = (int64_t)blob.size();
    uint32_t rec_cookie = get_be32(b);
    int32_t rec_size = (int32_t)get_be32(b + 12);
    if (rec_size != size) return {500, "size mismatch"};
    if (rec_cookie != cookie) return {404, "cookie mismatch"};
    int64_t data_off, data_len;
    if (!parse_needle_data(b, actual, size, version, &data_off, &data_len))
        return {500, "bad needle"};
    if (size > 0) {
        uint32_t stored = get_be32(b + kHeaderSize + size);
        uint32_t got = crc32c(b + data_off, (size_t)data_len);
        if (stored != got && stored != crc_legacy_value(got))
            return {500, "CRC error! Data On Disk Corrupted"};
    }
    std::string data = blob.substr((size_t)data_off, (size_t)data_len);
    if (version != 1 && data_len > 0 &&
        data_off + data_len < kHeaderSize + size) {
        uint8_t flags = b[data_off + data_len];
        if (flags & 0x01) {  // IS_COMPRESSED: stored gzip, serve plain
            std::string plain;
            if (!gunzip(data, &plain)) return {500, "bad gzip needle"};
            data.swap(plain);
        }
    }
    return {0, std::move(data)};
}

// EC read: .ecx binary search -> interval math -> local shard preads.
// Exactly ec_volume.py locate_needle/read_needle (themselves the
// bit-for-bit port of ec_locate.go + SearchNeedleFromSortedIndex,
// ec_volume.go:206-255); any non-local interval answers 307 so the
// Python ladder (remote fetch / reconstruct) takes over.
Reply handle_ec_read(const EcPtr& ev, uint64_t nid, uint32_t cookie) {
    int64_t lo = 0, hi = ev->ecx_entries.load() - 1;
    uint64_t off = 0;
    int32_t size = 0;
    bool found = false;
    uint8_t e[16];
    while (lo <= hi) {
        int64_t mid = lo + (hi - lo) / 2;
        if (!pread_full(ev->ecx_fd, e, 16, mid * 16))
            return {500, "ecx read failed"};
        uint64_t k = get_be64(e);
        if (k == nid) {
            off = (uint64_t)get_be32(e + 8) * kPaddingSize;
            size = (int32_t)get_be32(e + 12);
            found = true;
            break;
        }
        if (k < nid) lo = mid + 1;
        else hi = mid - 1;
    }
    if (!found) return {404, "not found"};
    if (size < 0) return {404, "already deleted"};
    int64_t shard_size = ev->shard_size.load();
    if (shard_size <= 0) return {307, "no local shards"};

    const int64_t lb = ev->large_block, sb = ev->small_block;
    const int64_t dat_size = 10 * shard_size;
    int64_t actual = get_actual_size(size, ev->version);
    // _locate_offset (ec_locate.go:55-75)
    int64_t large_row_size = lb * 10;
    int64_t rows_by_size = dat_size / large_row_size;
    int64_t block_index, inner;
    bool is_large;
    int64_t pos = (int64_t)off;
    if (pos < rows_by_size * large_row_size) {
        block_index = pos / lb;
        is_large = true;
        inner = pos % lb;
    } else {
        pos -= rows_by_size * large_row_size;
        block_index = pos / sb;
        is_large = false;
        inner = pos % sb;
    }
    // large-row count derivable from shard size (ec_locate.go:18-19)
    int64_t n_large_rows = (dat_size + 10 * sb) / (lb * 10);

    std::string blob((size_t)actual, '\0');
    int64_t want = actual, wrote = 0;
    while (want > 0) {
        int64_t block_len = is_large ? lb : sb;
        int64_t take = std::min(want, block_len - inner);
        // ToShardIdAndOffset (ec_locate.go:77-87)
        int64_t row = block_index / 10;
        int64_t ec_off = inner +
                         (is_large ? row * lb : n_large_rows * lb + row * sb);
        int sid = (int)(block_index % 10);
        int fd = ev->shard_fds[sid].load();
        if (fd >= 0) {
            if (!pread_full(fd, (uint8_t*)blob.data() + wrote,
                            (size_t)take, ec_off))
                return {500, "short shard read"};
        } else {
            // degraded read: rebuild this span from 10 local survivors
            // using the daemon-pushed recovery row; a wrong plan can
            // never serve silently — the needle CRC check downstream
            // rejects it
            EcRecovery rec;
            if (!ev->get_recovery(sid, &rec))
                return {307, "shard not local"};
            std::string sur((size_t)take, '\0');
            uint8_t* out = (uint8_t*)blob.data() + wrote;
            memset(out, 0, (size_t)take);
            const uint8_t (*mt)[256] = gf_mul();
            for (int j = 0; j < 10; j++) {
                int sfd = ev->shard_fds[rec.survivors[j]].load();
                if (sfd < 0) return {307, "survivor not local"};
                if (!pread_full(sfd, (uint8_t*)sur.data(), (size_t)take,
                                ec_off))
                    return {500, "short survivor read"};
                const uint8_t* row = mt[rec.coeffs[j]];
                const uint8_t* in = (const uint8_t*)sur.data();
                for (int64_t k = 0; k < take; k++) out[k] ^= row[in[k]];
            }
        }
        wrote += take;
        want -= take;
        block_index++;
        if (is_large && block_index == n_large_rows * 10) {
            is_large = false;
            block_index = 0;
        }
        inner = 0;
    }
    return finish_needle_read(blob, size, ev->version, cookie);
}

Reply handle_read(uint32_t vid, uint64_t nid, uint32_t cookie,
                  bool* was_ec = nullptr) {
    auto v = serving_vol(vid);
    if (!v) {
        auto ev = serving_ec(vid);
        if (ev) {
            if (was_ec) *was_ec = true;
            return handle_ec_read(ev, nid, cookie);
        }
        return {307, "volume not served natively"};
    }
    uint64_t off;
    int32_t size;
    {
        std::shared_lock<std::shared_mutex> lk(v->nm.mu);
        if (!v->nm.get(nid, &off, &size)) return {404, "not found"};
    }
    if (off == 0 || size == kTombstone) return {404, "not found"};
    if (size < 0) return {404, "already deleted"};
    int64_t actual = get_actual_size(size, v->version);
    std::string blob((size_t)actual, '\0');
    if (!pread_full(v->dat_fd, (uint8_t*)blob.data(), (size_t)actual,
                    (int64_t)off))
        return {500, "short read"};
    int64_t ttl = v->ttl_sec.load();
    if (ttl > 0) {
        // TTL volumes serve natively too; expired needles answer 404
        // exactly like the HTTP handler (volume_read.go:27-35)
        int64_t lm = needle_last_modified(
            (const uint8_t*)blob.data(), actual, size, v->version);
        int64_t now_s = (int64_t)(now_unix_ns() / 1000000000ull);
        if (lm > 0 && now_s >= lm + ttl) return {404, "expired"};
    }
    return finish_needle_read(blob, size, v->version, cookie);
}

// ---------------------------------------------------------------------------
// Replica fan-out: native->native framed forwarding for writes/deletes
// on replicated volumes (store_replicate.go:24-141: write locally, then
// every other location must succeed).  The daemon pushes each vid's
// peer fast-path addresses (svn_set_replicas); a write marked
// replicate ('R') never fans out again.
// ---------------------------------------------------------------------------

int fwd_connect(const std::string& addr) {
    size_t colon = addr.rfind(':');
    if (colon == std::string::npos) return -1;
    std::string host = addr.substr(0, colon);
    std::string port = addr.substr(colon + 1);
    struct addrinfo hints {};
    hints.ai_family = AF_UNSPEC;
    hints.ai_socktype = SOCK_STREAM;
    struct addrinfo* res = nullptr;
    if (getaddrinfo(host.c_str(), port.c_str(), &hints, &res) != 0)
        return -1;
    int fd = -1;
    for (auto* ai = res; ai; ai = ai->ai_next) {
        fd = socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
        if (fd < 0) continue;
        struct timeval tv {2, 0};
        setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
        struct timeval rtv {10, 0};
        setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &rtv, sizeof(rtv));
        if (connect(fd, ai->ai_addr, ai->ai_addrlen) == 0) break;
        close(fd);
        fd = -1;
    }
    freeaddrinfo(res);
    if (fd >= 0) {
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    }
    return fd;
}

bool fwd_send_all(int fd, const char* data, size_t n) {
    size_t sent = 0;
    while (sent < n) {
        ssize_t r = send(fd, data + sent, n - sent, MSG_NOSIGNAL);
        if (r <= 0) return false;
        sent += (size_t)r;
    }
    return true;
}

// Group-commit forward mux: concurrent forwards to one peer coalesce
// into a single pipelined batch on a shared connection (one send +
// in-order reply reads per batch, like the fsync ticket batching),
// instead of 2 syscalls each way per write on per-thread pooled
// sockets.  The peer's serve_conn drains pipelined frames from its
// buffered recv, so a batch of N costs O(1) wakeups on both sides.
struct FwdItem {
    const std::string* frame;
    uint32_t status = 0;
    bool reached = false;
    bool done = false;
};

struct FwdMux {
    std::mutex mu;
    std::condition_variable cv;
    std::deque<FwdItem*> queue;
    bool leader = false;  // a thread is running a batch on fd
    int fd = -1;          // only the leader touches the socket
};

std::mutex g_fwd_mu;
std::unordered_map<std::string, std::unique_ptr<FwdMux>> g_fwd_muxes;

FwdMux* fwd_mux(const std::string& addr) {
    std::lock_guard<std::mutex> lk(g_fwd_mu);
    auto& m = g_fwd_muxes[addr];
    if (!m) m.reset(new FwdMux());
    return m.get();
}

// Send every queued frame in one write, then read the replies back in
// order (replies on a fast-path connection are strictly sequential).
// Retries the whole batch once on a stale socket — safe for the same
// reason the old per-frame retry was: replicate writes/deletes are
// idempotent, and the Python fallback dedups identical rewrites.
void fwd_run_batch(FwdMux* mux, const std::string& addr,
                   std::vector<FwdItem*>& batch) {
    std::string out;
    size_t total = 0;
    for (FwdItem* it : batch) total += it->frame->size();
    out.reserve(total);
    for (FwdItem* it : batch) out += *it->frame;
    for (int attempt = 0; attempt < 2; attempt++) {
        if (mux->fd < 0) mux->fd = fwd_connect(addr);
        if (mux->fd < 0) return;  // peer unreachable: all stay !reached
        if (!fwd_send_all(mux->fd, out.data(), out.size())) {
            close(mux->fd);
            mux->fd = -1;
            continue;  // stale pooled socket: reconnect, resend batch
        }
        // buffered in-order reply parse: one recv drains many replies,
        // instead of two exact-size recvs per reply
        std::string rbuf;
        size_t off = 0;
        auto ensure = [&](size_t n) -> bool {
            while (rbuf.size() - off < n) {
                char tmp[16384];
                ssize_t r = recv(mux->fd, tmp, sizeof(tmp), 0);
                if (r <= 0) return false;
                rbuf.append(tmp, (size_t)r);
            }
            return true;
        };
        size_t i = 0;
        for (; i < batch.size(); i++) {
            if (!ensure(8)) break;
            const uint8_t* hdr = (const uint8_t*)rbuf.data() + off;
            uint32_t plen = get_be32(hdr + 4);
            batch[i]->status = get_be32(hdr);
            off += 8;
            if (plen && !ensure(plen)) break;
            off += plen;
            batch[i]->reached = true;
        }
        if (i == batch.size()) return;
        close(mux->fd);  // mid-batch drop: reconnect and retry once
        mux->fd = -1;
        for (FwdItem* it : batch) it->reached = false;
    }
}

// One framed request/reply against a peer fast-path port; returns false
// only when the peer is unreachable, otherwise *status carries the
// peer's reply code.  Requests riding in concurrently batch together.
bool fwd_request(const std::string& addr, const std::string& frame,
                 uint32_t* status) {
    FwdMux* mux = fwd_mux(addr);
    FwdItem item;
    item.frame = &frame;
    std::unique_lock<std::mutex> lk(mux->mu);
    mux->queue.push_back(&item);
    while (!item.done) {
        if (!mux->leader) {
            mux->leader = true;
            std::vector<FwdItem*> batch(mux->queue.begin(),
                                        mux->queue.end());
            mux->queue.clear();
            lk.unlock();
            fwd_run_batch(mux, addr, batch);
            lk.lock();
            for (FwdItem* it : batch) it->done = true;
            mux->leader = false;
            mux->cv.notify_all();
        } else {
            mux->cv.wait(lk, [&] {
                return item.done || !mux->leader;
            });
        }
    }
    *status = item.status;
    return item.reached;
}

// Fan a verified local write/delete out to the vid's other locations.
// 0 = all replicas acked; 307 = can't forward natively (the client
// falls back to the Python handler, whose fan-out + identical-rewrite
// dedup make the retry safe); 500 = a replica hard-failed.
uint32_t forward_to_replicas(uint32_t vid, const std::string& fid,
                             const std::string* body,
                             const std::string& jwt, int needed) {
    std::vector<std::string> addrs;
    {
        std::shared_lock<std::shared_mutex> lk(g_replica_mu);
        auto it = g_replicas.find(vid);
        if (it != g_replicas.end()) addrs = it->second;
    }
    if ((int)addrs.size() < needed) return 307;
    std::string frame;
    if (body) {
        frame = "W " + fid + " " + std::to_string(body->size());
        if (!jwt.empty()) frame += " " + jwt;
        frame += " R\n";
        frame += *body;
    } else {
        frame = "D " + fid;
        if (!jwt.empty()) frame += " " + jwt;
        frame += " R\n";
    }
    // one peer: forward inline; several: in parallel like the
    // reference's per-location goroutines (store_replicate.go:63-100),
    // so latency is max(peer RTTs) rather than their sum
    auto classify = [](bool reached, uint32_t status) -> uint32_t {
        if (!reached || status == 307) return 307;
        // 4xx from a peer = it cannot take framed replicate writes
        // (e.g. the Python read-only TCP loop answers 400, or its JWT
        // clock disagrees): hand the whole write to the Python handler
        // rather than failing it — only genuine replica errors (5xx)
        // fail the write, like store_replicate.go
        if (status >= 400 && status < 500) return 307;
        return status == 0 ? 0 : 500;
    };
    if (addrs.size() == 1) {
        uint32_t status = 0;
        bool reached = fwd_request(addrs[0], frame, &status);
        return classify(reached, status);
    }
    std::vector<uint32_t> results(addrs.size(), 500);
    std::vector<std::thread> threads;
    threads.reserve(addrs.size());
    for (size_t i = 0; i < addrs.size(); i++) {
        threads.emplace_back([&, i]() {
            uint32_t status = 0;
            bool reached = fwd_request(addrs[i], frame, &status);
            results[i] = classify(reached, status);
        });
    }
    for (auto& t : threads) t.join();
    uint32_t worst = 0;
    for (uint32_t r : results) {
        if (r == 500) return 500;  // hard replica failure wins
        if (r != 0) worst = r;     // else any 307 -> fallback
    }
    return worst;
}

std::string json_write_reply(int64_t size, uint32_t crc) {
    char etag[16];
    snprintf(etag, sizeof(etag), "%08x", crc);
    char out[96];
    snprintf(out, sizeof(out),
             "{\"name\": \"\", \"size\": %lld, \"eTag\": \"%s\"}",
             (long long)size, etag);
    return out;
}

Reply handle_write(uint32_t vid, uint64_t nid, uint32_t cookie,
                   const std::string& body, const std::string& fid,
                   bool is_replicate, const std::string& jwt) {
    auto v = serving_vol(vid);
    if (!v) return {307, "volume not served natively"};
    if (!v->writable.load() || v->read_only.load() || v->version != 3)
        return {307, "native writes disabled for this volume"};
    std::string wkey = jwt_key(true);
    if (!wkey.empty() && !jwt_verify(wkey, jwt, fid, true))
        return {401, "unauthorized"};
    int extra = v->extra_copies.load();
    if (!is_replicate && extra > 0) {
        // check forwardability BEFORE the local append: if the replica
        // set is unknown, 307 now and let the Python handler own the
        // whole replicated write
        std::shared_lock<std::shared_mutex> lk(g_replica_mu);
        auto it = g_replicas.find(vid);
        if (it == g_replicas.end() || (int)it->second.size() < extra)
            return {307, "replica set not configured"};
    }
    int64_t dlen = (int64_t)body.size();
    uint32_t crc = crc32c((const uint8_t*)body.data(), (size_t)dlen);
    // v3 needle with data + HAS_LAST_MODIFIED (what the HTTP write path
    // produces for a plain body: needle.py Needle.create), plus the
    // volume's TTL on TTL volumes (needle.py stamps ttl the same way;
    // without it the needle would never expire or vacuum)
    uint32_t ttl_raw = v->ttl_sec.load() > 0 ? v->ttl_raw.load() : 0;
    int64_t size = dlen
        ? 4 + dlen + 1 + kLastModifiedBytes + (ttl_raw ? kTtlBytes : 0)
        : 0;
    if (size > INT32_MAX) return {413, "entity too large"};

    // cookie check + identical-rewrite dedup against the existing needle
    // (volume_write.go:34-53,143-160)
    uint64_t old_off = 0;
    int32_t old_size = 0;
    bool have_old;
    {
        std::shared_lock<std::shared_mutex> lk(v->nm.mu);
        have_old = v->nm.get(nid, &old_off, &old_size);
        if ((int64_t)v->nm.content_bytes + get_actual_size(size, 3) >
            kMaxVolumeSize)
            return {500, "volume size limit exceeded"};
    }
    if (have_old && old_off > 0 && old_size >= 0) {
        uint8_t hdr[kHeaderSize];
        if (!pread_full(v->dat_fd, hdr, kHeaderSize, (int64_t)old_off))
            return {500, "short read"};
        uint32_t old_cookie = get_be32(hdr);
        if (old_cookie != cookie) return {403, "mismatching cookie"};
        if (old_size > 0) {
            // identical-rewrite dedup compares cookie + data only, like
            // isFileUnchanged (volume_write.go:34-53) — metadata such as
            // last-modified does not defeat it
            int64_t actual = get_actual_size(old_size, v->version);
            std::string old_blob((size_t)actual, '\0');
            int64_t doff, dl;
            if (pread_full(v->dat_fd, (uint8_t*)old_blob.data(),
                           (size_t)actual, (int64_t)old_off) &&
                parse_needle_data((const uint8_t*)old_blob.data(), actual,
                                  old_size, v->version, &doff, &dl) &&
                dl == dlen &&
                memcmp(old_blob.data() + doff, body.data(), (size_t)dlen)
                    == 0)
                return {0, json_write_reply(dlen, crc)};
        }
    }

    uint64_t append_ns = now_unix_ns();
    int64_t lastmod = (int64_t)(append_ns / 1000000000ull);
    int pad = padding_length(size, 3);
    int64_t rec_len = kHeaderSize + size + kChecksumSize + kTimestampSize + pad;
    std::string rec((size_t)rec_len, '\0');
    uint8_t* p = (uint8_t*)rec.data();
    put_be32(p, cookie);
    put_be64(p + 4, nid);
    put_be32(p + 12, (uint32_t)size);
    int64_t w = kHeaderSize;
    if (dlen) {
        put_be32(p + w, (uint32_t)dlen);
        w += 4;
        memcpy(p + w, body.data(), (size_t)dlen);
        w += dlen;
        p[w++] = ttl_raw ? (kFlagHasLastModified | kFlagHasTtl)
                         : kFlagHasLastModified;
        // 5-byte big-endian seconds (needle_write.go writes the low 5
        // bytes of the u64)
        for (int i = 0; i < kLastModifiedBytes; i++)
            p[w + i] =
                (uint8_t)(lastmod >> (8 * (kLastModifiedBytes - 1 - i)));
        w += kLastModifiedBytes;
        if (ttl_raw) {  // count, unit — after lastModified (needle.py)
            p[w++] = (uint8_t)((ttl_raw >> 8) & 0xFF);
            p[w++] = (uint8_t)(ttl_raw & 0xFF);
        }
    }
    put_be32(p + w, crc);
    w += 4;
    put_be64(p + w, append_ns);

    uint64_t ticket = 0;
    {
        std::lock_guard<std::mutex> lk(v->wmu);
        // re-check under the mutex: svn_quiesce (vacuum commit) flips
        // writable then drains wmu, so no append can land after it
        if (!v->writable.load() || v->read_only.load())
            return {307, "native writes disabled for this volume"};
        int64_t end = lseek(v->dat_fd, 0, SEEK_END);
        if (end < 0 ||
            !pwrite_full(v->dat_fd, (const uint8_t*)rec.data(),
                         (size_t)rec_len, end))
            return {500, "append failed"};
        std::unique_lock<std::shared_mutex> mlk(v->nm.mu);
        if (!append_idx_entry(v.get(), nid, (uint64_t)end, (int32_t)size))
            return {500, "idx append failed"};
        v->nm.apply(nid, (uint64_t)end, (int32_t)size);
        ticket = ++v->fs_seq;
    }
    if (append_ns > v->last_append_ns.load())
        v->last_append_ns.store(append_ns);
    if (lastmod > v->last_modified_ts.load())
        v->last_modified_ts.store(lastmod);
    if (v->do_fsync.load() && !v->fsync_ticket(ticket))
        return {500, "fsync failed"};
    if (!is_replicate && extra > 0) {
        uint32_t st = forward_to_replicas(vid, fid, &body, jwt, extra);
        if (st == 307)
            // local copy stands; the Python retry dedups it
            // (isFileUnchanged) and runs its own fan-out
            return {307, "replica fan-out unavailable"};
        if (st != 0) return {500, "replica write failed"};
    }
    return {0, json_write_reply(size, crc)};
}

Reply handle_delete(uint32_t vid, uint64_t nid, uint32_t cookie,
                    const std::string& fid, bool is_replicate,
                    const std::string& jwt) {
    auto v = serving_vol(vid);
    if (!v) return {307, "volume not served natively"};
    if (!v->writable.load() || v->read_only.load() || v->version != 3)
        return {307, "native writes disabled for this volume"};
    std::string wkey = jwt_key(true);
    if (!wkey.empty() && !jwt_verify(wkey, jwt, fid, true))
        return {401, "unauthorized"};
    int extra = v->extra_copies.load();
    if (!is_replicate && extra > 0) {
        std::shared_lock<std::shared_mutex> lk(g_replica_mu);
        auto it = g_replicas.find(vid);
        if (it == g_replicas.end() || (int)it->second.size() < extra)
            return {307, "replica set not configured"};
    }
    uint64_t old_off = 0;
    int32_t old_size = 0;
    bool absent;
    {
        std::shared_lock<std::shared_mutex> lk(v->nm.mu);
        absent = !v->nm.get(nid, &old_off, &old_size) || old_size < 0;
    }
    if (absent) {
        // absent locally — but a replica may still hold it (a
        // partially-failed earlier fan-out): replicate the delete
        // unconditionally like the Python handler (_delete_object ->
        // _replicate) so orphan copies get healed
        if (!is_replicate && extra > 0) {
            uint32_t st =
                forward_to_replicas(vid, fid, nullptr, jwt, extra);
            if (st == 307) return {307, "replica fan-out unavailable"};
            if (st != 0) return {500, "replica delete failed"};
        }
        return {0, "{\"size\": 0}"};
    }
    // tombstone needle: empty v3 record (volume.py delete_needle)
    uint64_t append_ns = now_unix_ns();
    int pad = padding_length(0, 3);
    int64_t rec_len = kHeaderSize + kChecksumSize + kTimestampSize + pad;
    std::string rec((size_t)rec_len, '\0');
    uint8_t* p = (uint8_t*)rec.data();
    put_be32(p, cookie);
    put_be64(p + 4, nid);
    put_be32(p + 12, 0);
    put_be64(p + kHeaderSize + kChecksumSize, append_ns);
    uint64_t ticket = 0;
    {
        std::lock_guard<std::mutex> lk(v->wmu);
        if (!v->writable.load() || v->read_only.load())
            return {307, "native writes disabled for this volume"};
        int64_t end = lseek(v->dat_fd, 0, SEEK_END);
        if (end < 0 ||
            !pwrite_full(v->dat_fd, (const uint8_t*)rec.data(),
                         (size_t)rec_len, end))
            return {500, "append failed"};
        std::unique_lock<std::shared_mutex> mlk(v->nm.mu);
        if (!append_idx_entry(v.get(), nid, (uint64_t)end, kTombstone))
            return {500, "idx append failed"};
        v->nm.apply(nid, 0, kTombstone);
        ticket = ++v->fs_seq;
    }
    if (append_ns > v->last_append_ns.load())
        v->last_append_ns.store(append_ns);
    if (v->do_fsync.load() && !v->fsync_ticket(ticket))
        return {500, "fsync failed"};
    if (!is_replicate && extra > 0) {
        uint32_t st = forward_to_replicas(vid, fid, nullptr, jwt, extra);
        if (st == 307) return {307, "replica fan-out unavailable"};
        if (st != 0) return {500, "replica delete failed"};
    }
    char out[48];
    snprintf(out, sizeof(out), "{\"size\": %d}", old_size);
    return {0, out};
}

// ---------------------------------------------------------------------------
// Assign-lease pool: the master leases contiguous fid key ranges to the
// engine, which answers per-file assigns ("A [count]\n") off the GIL.
// The reference master serves /dir/assign from compiled Go
// (master_server_handlers.go:102-165); a GIL-bound Python handler caps
// per-file-assign workloads, so the Python master keeps authority
// (placement, growth, sequencing) and refills bounded leases here.
// ---------------------------------------------------------------------------

struct AssignLease {
    uint32_t vid;
    std::string url, public_url;
    std::atomic<uint64_t> next;
    uint64_t end;
    std::chrono::steady_clock::time_point born;
};

std::shared_mutex g_lease_mu;
std::vector<std::shared_ptr<AssignLease>> g_leases;
std::atomic<size_t> g_lease_rr{0};
std::atomic<uint64_t> g_assign_rng{0x9E3779B97F4A7C15ull};

uint64_t assign_rand() {
    // xorshift* — cookies need uniqueness pressure, not crypto (the
    // Python master uses random.getrandbits(32))
    uint64_t x = g_assign_rng.fetch_add(0x9E3779B97F4A7C15ull);
    x ^= x >> 30;
    x *= 0xBF58476D1CE4E5B9ull;
    x ^= x >> 27;
    x *= 0x94D049BB133111EBull;
    return x ^ (x >> 31);
}

// -> JSON assign reply or empty when no lease can cover `count`
std::string assign_take(int64_t count) {
    std::shared_lock<std::shared_mutex> lk(g_lease_mu);
    size_t n = g_leases.size();
    for (size_t attempt = 0; attempt < n; attempt++) {
        auto& lease = g_leases[g_lease_rr.fetch_add(1) % n];
        // CAS, not fetch_add: an oversized request must not burn the
        // lease's remaining keys on its way to failing
        uint64_t key = lease->next.load();
        bool got = false;
        while (key + (uint64_t)count <= lease->end + 1 &&
               key <= lease->end) {
            if (lease->next.compare_exchange_weak(
                    key, key + (uint64_t)count)) {
                got = true;
                break;
            }
        }
        if (!got) continue;  // exhausted or count doesn't fit: next lease
        uint32_t cookie = (uint32_t)assign_rand();
        char fid[64];
        snprintf(fid, sizeof(fid), "%u,%llx%08x", lease->vid,
                 (unsigned long long)key, cookie);
        std::string out = "{\"fid\": \"";
        out += fid;
        out += "\", \"url\": \"" + lease->url + "\", \"publicUrl\": \"" +
               lease->public_url + "\", \"count\": " +
               std::to_string(count);
        // JWT-secured clusters: mint the fid-scoped write token the
        // master would have attached (/dir/assign "auth" field)
        std::string wkey = jwt_key(true);
        if (!wkey.empty()) {
            int exp;
            {
                std::lock_guard<std::mutex> jlk(g_jwt_mu);
                exp = g_jwt_expire_s;
            }
            out += ", \"auth\": \"" + jwt_mint(wkey, fid, exp) + "\"";
        }
        out += "}";
        return out;
    }
    return "";
}

// ---------------------------------------------------------------------------
// Framed-TCP server (same wire protocol as the Python TCP fast path:
// text command line, ">II"-framed replies)
// ---------------------------------------------------------------------------

struct Server {
    int listen_fd = -1;
    std::atomic<bool> stop{false};
    std::atomic<int> active_conns{0};
    std::thread accept_thread;
    std::mutex conns_mu;
    std::vector<int> conns;
};

Server* g_server = nullptr;
std::mutex g_server_mu;
std::string g_http_redirect;  // "host:port" of the full HTTP handler
std::atomic<int> g_server_port{0};  // bound port (0 = not running)

bool recv_some(int fd, std::string& buf);

// Minimal HTTP/1.1 reply on the fast-path port (keep-alive).  Only
// plain needle GET/HEADs are answered here; anything else 302s to the
// full Python handler (g_http_redirect).
bool send_http_reply(int fd, int status, const char* reason,
                     const std::string& body, bool head,
                     const std::string& extra_headers) {
    // compose in std::string: extra_headers carries a client-chosen
    // request target (302 Location), so no fixed-size buffer is safe
    std::string out = "HTTP/1.1 " + std::to_string(status) + " " + reason +
                      "\r\nContent-Length: " + std::to_string(body.size()) +
                      "\r\nContent-Type: application/octet-stream\r\n" +
                      extra_headers + "Connection: keep-alive\r\n\r\n";
    if (!head) out += body;
    size_t sent = 0;
    while (sent < out.size()) {
        ssize_t r = send(fd, out.data() + sent, out.size() - sent, 0);
        if (r <= 0) return false;
        sent += (size_t)r;
    }
    return true;
}

// Percent-escape control characters in a client-supplied request target
// before echoing it into a Location header — a bare CR/LF (or any
// control byte) in the target must never become header structure.
std::string sanitize_target(const std::string& target) {
    std::string out;
    out.reserve(target.size());
    for (unsigned char c : target) {
        if (c < 0x21 || c == 0x7f) {
            char esc[4];
            snprintf(esc, sizeof(esc), "%%%02X", c);
            out += esc;
        } else {
            out += (char)c;
        }
    }
    return out;
}

// Handle one HTTP request whose request line is already parsed off
// `buf` (headers still pending).  Returns false to drop the connection.
bool serve_http_request(Server* srv, int fd, const std::string& method,
                        const std::string& raw_target, std::string& buf) {
    // drain headers until the blank line; keep the bearer token in case
    // the cluster signs reads
    std::string auth_jwt;
    for (;;) {
        size_t nl;
        while ((nl = buf.find('\n')) == std::string::npos) {
            if (!recv_some(fd, buf)) return false;
            if (srv->stop.load()) return false;
        }
        std::string line = buf.substr(0, nl);
        buf.erase(0, nl + 1);
        if (!line.empty() && line.back() == '\r') line.pop_back();
        if (line.empty()) break;
        if (line.size() > 15 &&
            strncasecmp(line.c_str(), "authorization:", 14) == 0) {
            size_t p = 14;
            while (p < line.size() && line[p] == ' ') p++;
            if (strncasecmp(line.c_str() + p, "bearer ", 7) == 0)
                auth_jwt = line.substr(p + 7);
        }
    }
    bool head = (method == "HEAD");
    const std::string target = sanitize_target(raw_target);
    std::string path = target;
    size_t q = path.find('?');
    bool has_query = q != std::string::npos;
    if (has_query) {
        // a bare ?jwt=<token> stays on the fast path (the reference's
        // query-parameter token convention, security/jwt.go GetJwt);
        // any other parameter means full-handler semantics -> 302
        std::string query = path.substr(q + 1);
        path = path.substr(0, q);
        bool only_jwt = true;
        size_t pos = 0;
        while (pos <= query.size() && only_jwt) {
            size_t amp = query.find('&', pos);
            std::string kv = query.substr(
                pos, amp == std::string::npos ? std::string::npos
                                              : amp - pos);
            if (!kv.empty()) {
                if (kv.rfind("jwt=", 0) == 0) {
                    if (auth_jwt.empty()) auth_jwt = kv.substr(4);
                } else {
                    only_jwt = false;
                }
            }
            if (amp == std::string::npos) break;
            pos = amp + 1;
        }
        has_query = !only_jwt;
    }
    uint32_t vid;
    uint64_t nid;
    uint32_t cookie;
    std::string fid = path.substr(path.find('/') == 0 ? 1 : 0);
    // volume-server fid paths may use "vid/fid" form; normalize to comma
    size_t slash = fid.find('/');
    if (slash != std::string::npos) fid[slash] = ',';
    if (has_query || !parse_fid(fid, &vid, &nid, &cookie)) {
        g_stat_fallbacks.fetch_add(1);  // 302 = the HTTP-shaped 307
        if (g_http_redirect.empty())
            return send_http_reply(fd, 404, "Not Found", "not found",
                                   head, "");
        return send_http_reply(
            fd, 302, "Found", "", head,
            "Location: http://" + g_http_redirect + target + "\r\n");
    }
    std::string rkey = jwt_key(false);
    if (!rkey.empty() && !jwt_verify(rkey, auth_jwt, fid, false)) {
        count_reply(401);
        return send_http_reply(fd, 401, "Unauthorized", "unauthorized",
                               head, "");
    }
    Reply r = handle_read(vid, nid, cookie);
    count_reply(r.status);
    if (r.status == 0)
        return send_http_reply(fd, 200, "OK", r.payload, head,
                               "Accept-Ranges: bytes\r\n");
    if (r.status == 307) {
        if (g_http_redirect.empty())
            return send_http_reply(fd, 404, "Not Found", r.payload, head,
                                   "");
        return send_http_reply(
            fd, 302, "Found", "", head,
            "Location: http://" + g_http_redirect + target + "\r\n");
    }
    if (r.status == 404)
        return send_http_reply(fd, 404, "Not Found", r.payload, head, "");
    return send_http_reply(fd, 500, "Internal Server Error", r.payload,
                           head, "");
}

bool recv_some(int fd, std::string& buf) {
    char tmp[16384];
    ssize_t r = recv(fd, tmp, sizeof(tmp), 0);
    if (r <= 0) return false;
    buf.append(tmp, (size_t)r);
    return true;
}

// Reply outbox: framed replies accumulate and go out in one send just
// before the connection would block on recv.  A pipelined batch (the
// replica side of the forward mux) then costs one reply syscall and
// one peer wakeup instead of one per frame; unpipelined clients see a
// flush per request, exactly like the old per-reply writev.
struct Outbox {
    int fd;
    std::string pending;

    bool queue(uint32_t status, const std::string& payload) {
        size_t n = pending.size();
        pending.resize(n + 8);
        put_be32((uint8_t*)&pending[n], status);
        put_be32((uint8_t*)&pending[n] + 4, (uint32_t)payload.size());
        pending += payload;
        if (pending.size() >= 131072) return flush();
        return true;
    }

    bool flush() {
        if (pending.empty()) return true;
        bool ok = fwd_send_all(fd, pending.data(), pending.size());
        pending.clear();
        return ok;
    }
};

void serve_conn(Server* srv, int fd) {
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    std::string buf;
    Outbox ob{fd};
    while (!srv->stop.load()) {
        size_t nl;
        while ((nl = buf.find('\n')) == std::string::npos) {
            if (!ob.flush() || !recv_some(fd, buf)) goto done;
            if (srv->stop.load()) goto done;
        }
        {
            std::string line = buf.substr(0, nl);
            buf.erase(0, nl + 1);
            if (!line.empty() && line.back() == '\r') line.pop_back();
            // tokenize
            std::vector<std::string> parts;
            size_t i = 0;
            while (i < line.size()) {
                while (i < line.size() && line[i] == ' ') i++;
                size_t j = i;
                while (j < line.size() && line[j] != ' ') j++;
                if (j > i) parts.push_back(line.substr(i, j - i));
                i = j;
            }
            if (parts.empty()) {
                if (!ob.queue(400, "bad request")) goto done;
                continue;
            }
            const std::string& op = parts[0];
            uint32_t vid;
            uint64_t nid;
            uint32_t cookie;
            if ((op == "GET" || op == "HEAD") && parts.size() == 3) {
                // plain HTTP clients may hit the fast-path port too
                g_stat_http_reads.fetch_add(1);
                if (!ob.flush() ||
                    !serve_http_request(srv, fd, op, parts[1], buf))
                    goto done;
            } else if (op == "G"
                       && (parts.size() == 2 || parts.size() == 3)) {
                if (!parse_fid(parts[1], &vid, &nid, &cookie)) {
                    g_stat_reads.fetch_add(1);
                    g_stat_errors.fetch_add(1);
                    if (!ob.queue(400, "bad fid")) goto done;
                    continue;
                }
                std::string rkey = jwt_key(false);
                if (!rkey.empty() &&
                    !jwt_verify(rkey,
                                parts.size() == 3 ? parts[2] : "",
                                parts[1], false)) {
                    g_stat_reads.fetch_add(1);
                    count_reply(401);
                    if (!ob.queue(401, "unauthorized")) goto done;
                    continue;
                }
                bool was_ec = false;
                Reply r = handle_read(vid, nid, cookie, &was_ec);
                // exactly one type per request: framed reads split into
                // read/ec_read by the path that served them
                (was_ec ? g_stat_ec_reads : g_stat_reads).fetch_add(1);
                count_reply(r.status);
                if (!ob.queue(r.status, r.payload)) goto done;
            } else if (op == "W" && parts.size() >= 3
                       && parts.size() <= 5) {
                errno = 0;
                long long blen = strtoll(parts[2].c_str(), nullptr, 10);
                if (errno || blen < 0 || blen > INT32_MAX) {
                    // body length unknowable: the stream cannot be
                    // resynchronized, so reply and drop the connection
                    ob.queue(400, "bad length");
                    goto done;
                }
                while (buf.size() < (size_t)blen) {
                    if (!ob.flush() || !recv_some(fd, buf)) goto done;
                }
                std::string body = buf.substr(0, (size_t)blen);
                buf.erase(0, (size_t)blen);
                if (!parse_fid(parts[1], &vid, &nid, &cookie)) {
                    // body already drained: framing stays intact
                    if (!ob.queue(400, "bad fid")) goto done;
                    continue;
                }
                // optional trailing tokens: a write JWT and/or the
                // replicate marker "R" (a JWT always contains '.')
                std::string jwt;
                bool is_replicate = false;
                for (size_t t = 3; t < parts.size(); t++) {
                    if (parts[t] == "R") is_replicate = true;
                    else if (parts[t] != "-") jwt = parts[t];
                }
                g_stat_writes.fetch_add(1);
                Reply r = handle_write(vid, nid, cookie, body, parts[1],
                                       is_replicate, jwt);
                count_reply(r.status);
                if (!ob.queue(r.status, r.payload)) goto done;
            } else if (op == "A" && parts.size() <= 2) {
                long long count = 1;
                if (parts.size() == 2) {
                    errno = 0;
                    count = strtoll(parts[1].c_str(), nullptr, 10);
                    if (errno || count <= 0 || count > 1000000) {
                        if (!ob.queue(400, "bad count")) goto done;
                        continue;
                    }
                }
                std::string out = assign_take(count);
                if (out.empty()) {
                    // no live lease: the client retries /dir/assign
                    if (!ob.queue(503, "no assign lease"))
                        goto done;
                    continue;
                }
                if (!ob.queue(0, out)) goto done;
            } else if (op == "D" && parts.size() >= 2
                       && parts.size() <= 4) {
                g_stat_deletes.fetch_add(1);
                if (!parse_fid(parts[1], &vid, &nid, &cookie)) {
                    if (!ob.queue(400, "bad fid")) goto done;
                    continue;
                }
                std::string jwt;
                bool is_replicate = false;
                for (size_t t = 2; t < parts.size(); t++) {
                    if (parts[t] == "R") is_replicate = true;
                    else if (parts[t] != "-") jwt = parts[t];
                }
                Reply r = handle_delete(vid, nid, cookie, parts[1],
                                        is_replicate, jwt);
                count_reply(r.status);
                if (!ob.queue(r.status, r.payload)) goto done;
            } else {
                if (!ob.queue(400, "bad request")) goto done;
            }
        }
    }
done:
    ob.flush();  // best effort: drop queued replies with the conn
    close(fd);
    {
        std::lock_guard<std::mutex> lk(srv->conns_mu);
        for (auto it = srv->conns.begin(); it != srv->conns.end(); ++it) {
            if (*it == fd) {
                srv->conns.erase(it);
                break;
            }
        }
    }
    // LAST touch of srv: svn_server_stop spins on this before delete
    srv->active_conns.fetch_sub(1);
}

}  // namespace

extern "C" {

// -- assign leases ----------------------------------------------------------

int svn_assign_add_lease(uint32_t vid, const char* url,
                         const char* public_url, uint64_t key_start,
                         uint64_t key_end) {
    auto lease = std::make_shared<AssignLease>();
    lease->vid = vid;
    lease->url = url;
    lease->public_url = public_url && *public_url ? public_url : url;
    lease->next.store(key_start);
    lease->end = key_end;
    lease->born = std::chrono::steady_clock::now();
    std::unique_lock<std::shared_mutex> lk(g_lease_mu);
    g_leases.push_back(std::move(lease));
    return 0;
}

// Remaining assignable keys across live leases; prunes exhausted ones
// and (when max_age_ms > 0) leases older than max_age_ms, so placement
// staleness expires per-lease instead of via a global clear that would
// stall every assigner at once.
int64_t svn_assign_remaining(int64_t max_age_ms) {
    auto now = std::chrono::steady_clock::now();
    std::unique_lock<std::shared_mutex> lk(g_lease_mu);
    int64_t total = 0;
    for (auto it = g_leases.begin(); it != g_leases.end();) {
        uint64_t next = (*it)->next.load();
        bool expired =
            max_age_ms > 0 &&
            std::chrono::duration_cast<std::chrono::milliseconds>(
                now - (*it)->born)
                    .count() > max_age_ms;
        if (next > (*it)->end || expired) {
            it = g_leases.erase(it);
        } else {
            total += (int64_t)((*it)->end - next + 1);
            ++it;
        }
    }
    return total;
}

int svn_assign_clear() {
    std::unique_lock<std::shared_mutex> lk(g_lease_mu);
    g_leases.clear();
    return 0;
}

// Where the fast-path port 302s HTTP requests it cannot serve (the
// volume server's full handler).  Set before svn_server_start.
int svn_server_set_redirect(const char* addr) {
    std::lock_guard<std::mutex> lk(g_server_mu);
    g_http_redirect = addr ? addr : "";
    return 0;
}

// Start the native fast-path server; returns the bound port or -errno.
int svn_server_start(const char* host, int port) {
    std::lock_guard<std::mutex> lk(g_server_mu);
    if (g_server) return -EALREADY;
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -errno;
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
        // hostname (e.g. "localhost", a configured DNS name): resolve it
        // rather than silently binding loopback and advertising a port
        // nobody can reach
        struct addrinfo hints {};
        hints.ai_family = AF_INET;
        hints.ai_socktype = SOCK_STREAM;
        struct addrinfo* res = nullptr;
        if (getaddrinfo(host, nullptr, &hints, &res) != 0 || !res) {
            close(fd);
            return -EADDRNOTAVAIL;
        }
        addr.sin_addr = ((sockaddr_in*)res->ai_addr)->sin_addr;
        freeaddrinfo(res);
    }
    if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
        // requested port taken: fall back to ephemeral (clients discover
        // the real port via /admin/status, volume_server/server.py)
        addr.sin_port = 0;
        if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
            int e = errno;
            close(fd);
            return -e;
        }
    }
    if (listen(fd, 256) != 0) {
        int e = errno;
        close(fd);
        return -e;
    }
    socklen_t alen = sizeof(addr);
    getsockname(fd, (sockaddr*)&addr, &alen);
    int bound = ntohs(addr.sin_port);
    auto* srv = new Server();
    srv->listen_fd = fd;
    srv->accept_thread = std::thread([srv]() {
        while (!srv->stop.load()) {
            int cfd = accept(srv->listen_fd, nullptr, nullptr);
            if (cfd < 0) {
                if (srv->stop.load()) return;
                continue;
            }
            {
                std::lock_guard<std::mutex> lk(srv->conns_mu);
                srv->conns.push_back(cfd);
            }
            srv->active_conns.fetch_add(1);
            std::thread(serve_conn, srv, cfd).detach();
        }
    });
    g_server = srv;
    g_server_port.store(bound);
    return bound;
}

// Bound port of the process-wide native listener (0 = none).  In
// combined master+volume processes the registry is shared, so whichever
// daemon started the listener serves every command (incl. assigns).
int svn_server_port() { return g_server_port.load(); }

// out[0..6] = framed reads, ec reads, writes, deletes, http reads,
//             307 fallbacks, errors
int svn_server_stats(int64_t* out) {
    out[0] = g_stat_reads.load();
    out[1] = g_stat_ec_reads.load();
    out[2] = g_stat_writes.load();
    out[3] = g_stat_deletes.load();
    out[4] = g_stat_http_reads.load();
    out[5] = g_stat_fallbacks.load();
    out[6] = g_stat_errors.load();
    return 0;
}

int svn_server_stop() {
    std::lock_guard<std::mutex> lk(g_server_mu);
    if (!g_server) return 0;
    Server* srv = g_server;
    g_server = nullptr;
    g_server_port.store(0);
    srv->stop.store(true);
    shutdown(srv->listen_fd, SHUT_RDWR);
    close(srv->listen_fd);
    {
        std::lock_guard<std::mutex> clk(srv->conns_mu);
        for (int fd : srv->conns) shutdown(fd, SHUT_RDWR);
    }
    if (srv->accept_thread.joinable()) srv->accept_thread.join();
    // conn threads are detached: wait until every one has made its final
    // touch of srv (bounded; on timeout leak rather than use-after-free)
    for (int i = 0; i < 500 && srv->active_conns.load() > 0; i++)
        std::this_thread::sleep_for(std::chrono::milliseconds(10));
    if (srv->active_conns.load() == 0) delete srv;
    return 0;
}

// ---------------------------------------------------------------------------
// Benchmark load generator (native-speed client, like the reference's
// compiled `weed benchmark` driver)
// ---------------------------------------------------------------------------

// op: 'W' writes fid[i] with a `payload_size` body; 'R' reads a random
// fid.  fids = '\n'-joined fid strings.  lat_us_out (length nreqs) gets
// per-request latency in microseconds.  Returns elapsed seconds; errors
// counted into *errors_out.
double svn_bench(const char* host, int port, int op, const char* fids,
                 int64_t nfids, int64_t nreqs, int payload_size,
                 int concurrency, float* lat_us_out, int64_t* errors_out) {
    std::vector<std::string> fid_list;
    fid_list.reserve((size_t)nfids);
    {
        const char* p = fids;
        for (int64_t i = 0; i < nfids; i++) {
            const char* e = strchr(p, '\n');
            if (!e) {
                fid_list.emplace_back(p);
                break;
            }
            fid_list.emplace_back(p, e - p);
            p = e + 1;
        }
    }
    if (fid_list.empty() || nreqs <= 0) return 0.0;
    std::string payload((size_t)payload_size, 'x');
    for (size_t i = 0; i < payload.size(); i++)
        payload[i] = (char)('a' + (i * 131 + 7) % 26);
    std::atomic<int64_t> next{0};
    std::atomic<int64_t> errors{0};
    std::atomic<int64_t> completed{0};

    auto dial = [](const std::string& h, int p) -> int {
        int fd = socket(AF_INET, SOCK_STREAM, 0);
        if (fd < 0) return -1;
        sockaddr_in addr{};
        addr.sin_family = AF_INET;
        addr.sin_port = htons((uint16_t)p);
        if (inet_pton(AF_INET, h.c_str(), &addr.sin_addr) != 1)
            addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
        if (connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
            close(fd);
            return -1;
        }
        int one = 1;
        setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        return fd;
    };

    auto worker = [&](int widx) {
        int fd = dial(host, port);
        if (fd < 0) return;  // surviving workers drain the slots;
                             // unclaimed slots are charged as errors
        std::mt19937_64 rng(0x5EEDu + (unsigned)widx);
        std::string rxbuf;
        std::string req;

        // framed request/response on an arbitrary conn (F mode talks to
        // the master AND per-volume-server conns)
        auto framed = [&](int cfd, std::string& rbuf,
                          const std::string& frame, uint32_t* st,
                          std::string* payload) -> bool {
            size_t sent = 0;
            while (sent < frame.size()) {
                ssize_t r = send(cfd, frame.data() + sent,
                                 frame.size() - sent, 0);
                if (r <= 0) return false;
                sent += (size_t)r;
            }
            while (rbuf.size() < 8)
                if (!recv_some(cfd, rbuf)) return false;
            *st = get_be32((const uint8_t*)rbuf.data());
            uint32_t plen = get_be32((const uint8_t*)rbuf.data() + 4);
            while (rbuf.size() < 8 + (size_t)plen)
                if (!recv_some(cfd, rbuf)) return false;
            if (payload) *payload = rbuf.substr(8, plen);
            rbuf.erase(0, 8 + (size_t)plen);
            return true;
        };
        std::unordered_map<std::string, int> vol_conns;
        std::unordered_map<std::string, std::string> vol_bufs;

        auto json_field = [](const std::string& j,
                             const char* key) -> std::string {
            std::string pat = std::string("\"") + key + "\": \"";
            size_t p = j.find(pat);
            if (p == std::string::npos) return "";
            p += pat.size();
            size_t e = j.find('"', p);
            return e == std::string::npos ? "" : j.substr(p, e - p);
        };

        while (true) {
            int64_t slot = next.fetch_add(1);
            if (slot >= nreqs) break;
            if (op == 'F') {
                // full per-file cycle: native assign -> native write
                // (the reference benchmark's per-file flow,
                // command/benchmark.go writeFiles)
                auto t0 = std::chrono::steady_clock::now();
                uint32_t st = 500;
                std::string assign;
                bool master_ok = framed(fd, rxbuf, "A\n", &st, &assign);
                // a 503 is a transient lease drought (refill ticks every
                // 0.2 s): wait briefly like a real client would fall
                // back, instead of charging an instant error
                for (int retry = 0; master_ok && st == 503 && retry < 50;
                     retry++) {
                    std::this_thread::sleep_for(
                        std::chrono::milliseconds(5));
                    master_ok = framed(fd, rxbuf, "A\n", &st, &assign);
                }
                bool ok = master_ok && st == 0;
                if (ok) {
                    std::string fid = json_field(assign, "fid");
                    std::string url = json_field(assign, "url");
                    size_t colon = url.rfind(':');
                    if (fid.empty() || colon == std::string::npos) {
                        ok = false;
                    } else {
                        auto it = vol_conns.find(url);
                        if (it == vol_conns.end()) {
                            int vport =
                                atoi(url.c_str() + colon + 1) + 20000;
                            int vfd =
                                dial(url.substr(0, colon), vport);
                            if (vfd >= 0) {
                                it = vol_conns.emplace(url, vfd).first;
                                vol_bufs.emplace(url, std::string());
                            }
                            // a failed dial is NOT cached: the server
                            // may just not be listening yet
                        }
                        if (it == vol_conns.end()) {
                            ok = false;
                        } else {
                            std::string auth = json_field(assign, "auth");
                            std::string wreq =
                                "W " + fid + " " +
                                std::to_string(payload.size());
                            if (!auth.empty()) wreq += " " + auth;
                            wreq += "\n";
                            wreq += payload;
                            if (!framed(it->second, vol_bufs[url], wreq,
                                        &st, nullptr)) {
                                // dead volume conn: drop it so the next
                                // slot re-dials
                                close(it->second);
                                vol_conns.erase(it);
                                vol_bufs.erase(url);
                                ok = false;
                            } else {
                                ok = st == 0;
                            }
                        }
                    }
                }
                auto t1 = std::chrono::steady_clock::now();
                if (lat_us_out)
                    lat_us_out[slot] =
                        (float)std::chrono::duration_cast<
                            std::chrono::nanoseconds>(t1 - t0)
                            .count() /
                        1000.0f;
                completed.fetch_add(1);
                if (!ok) errors.fetch_add(1);
                if (!master_ok) break;  // master conn dead: surviving
                                        // workers drain the slots
                continue;
            }
            const std::string& entry =
                (op == 'W') ? fid_list[(size_t)(slot % nfids)]
                            : fid_list[rng() % fid_list.size()];
            // a list entry may carry a per-fid token: "fid jwt"
            // (JWT-secured clusters; the Python driver joins them)
            size_t sp = entry.find(' ');
            std::string fid = entry.substr(0, sp);
            std::string tok =
                sp == std::string::npos ? "" : entry.substr(sp + 1);
            req.clear();
            auto t0 = std::chrono::steady_clock::now();
            if (op == 'W') {
                req = "W " + fid + " " + std::to_string(payload.size());
                if (!tok.empty()) req += " " + tok;
                req += "\n";
                req += payload;
            } else if (op == 'D') {
                req = "D " + fid;
                if (!tok.empty()) req += " " + tok;
                req += "\n";
            } else if (op == 'H') {  // HTTP GET against the same port
                req = "GET /" + fid + " HTTP/1.1\r\nHost: bench\r\n\r\n";
            } else {
                req = "G " + fid;
                if (!tok.empty()) req += " " + tok;
                req += "\n";
            }
            size_t sent = 0;
            bool ok = true;
            while (sent < req.size()) {
                ssize_t r = send(fd, req.data() + sent, req.size() - sent, 0);
                if (r <= 0) {
                    ok = false;
                    break;
                }
                sent += (size_t)r;
            }
            uint32_t status = 500, plen = 0;
            if (ok && op == 'H') {
                // parse an HTTP/1.1 keep-alive response
                size_t hdr_end;
                while ((hdr_end = rxbuf.find("\r\n\r\n"))
                       == std::string::npos) {
                    if (!recv_some(fd, rxbuf)) {
                        ok = false;
                        break;
                    }
                }
                if (ok) {
                    status = (uint32_t)atoi(rxbuf.c_str() + 9);
                    if (status == 200) status = 0;
                    size_t clpos = rxbuf.find("Content-Length: ");
                    size_t body_len = 0;
                    if (clpos != std::string::npos && clpos < hdr_end)
                        body_len = (size_t)atoll(rxbuf.c_str() + clpos + 16);
                    size_t total = hdr_end + 4 + body_len;
                    while (rxbuf.size() < total) {
                        if (!recv_some(fd, rxbuf)) {
                            ok = false;
                            break;
                        }
                    }
                    if (ok) rxbuf.erase(0, total);
                }
            } else if (ok) {
                while (rxbuf.size() < 8) {
                    if (!recv_some(fd, rxbuf)) {
                        ok = false;
                        break;
                    }
                }
                if (ok) {
                    status = get_be32((const uint8_t*)rxbuf.data());
                    plen = get_be32((const uint8_t*)rxbuf.data() + 4);
                    while (rxbuf.size() < 8 + (size_t)plen) {
                        if (!recv_some(fd, rxbuf)) {
                            ok = false;
                            break;
                        }
                    }
                    if (ok) rxbuf.erase(0, 8 + (size_t)plen);
                }
            }
            auto t1 = std::chrono::steady_clock::now();
            if (lat_us_out)
                lat_us_out[slot] =
                    (float)std::chrono::duration_cast<
                        std::chrono::nanoseconds>(t1 - t0)
                        .count() /
                    1000.0f;
            completed.fetch_add(1);
            if (!ok || status != 0) errors.fetch_add(1);
            if (!ok) break;  // connection dead
        }
        for (auto& kv : vol_conns)
            if (kv.second >= 0) close(kv.second);
        close(fd);
    };

    auto start = std::chrono::steady_clock::now();
    std::vector<std::thread> threads;
    for (int i = 0; i < concurrency; i++) threads.emplace_back(worker, i);
    for (auto& t : threads) t.join();
    auto end = std::chrono::steady_clock::now();
    if (errors_out)
        *errors_out = errors.load() + (nreqs - completed.load());
    return std::chrono::duration<double>(end - start).count();
}

}  // extern "C"

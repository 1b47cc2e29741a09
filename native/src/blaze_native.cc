// Native host-side kernels for blaze_tpu.
//
// The reference implements its entire engine in Rust; here the TPU executes
// the columnar compute (JAX/XLA) and this library accelerates the host-side
// runtime hot paths the reference also keeps native: byte-plane transpose
// for shuffle/spill compression (reference: io/batch_serde.rs TransposeOpt),
// spark-exact murmur3/xxhash64 over variable-length byte arrays (reference:
// hash/mur.rs, hash/xxhash.rs — bit-exactness mandatory for partition
// routing), and zstd frame codecs. Exposed via a plain C ABI consumed with
// ctypes (pybind11 is not available in this environment).

#include <cstdint>
#include <cstring>
#include <cstddef>

#ifdef HAVE_ZSTD
#include <zstd.h>
#endif

#define EXPORT extern "C" __attribute__((visibility("default")))

// ---------------------------------------------------------------------------
// byte-plane transpose: (n, itemsize) <-> (itemsize, n), cache-blocked
// ---------------------------------------------------------------------------

EXPORT void bt_transpose(const uint8_t* src, uint8_t* dst, size_t n,
                         size_t itemsize, int forward) {
  constexpr size_t BLOCK = 512;
  if (forward) {  // row-major values -> byte planes
    for (size_t b = 0; b < n; b += BLOCK) {
      size_t end = b + BLOCK < n ? b + BLOCK : n;
      for (size_t k = 0; k < itemsize; ++k) {
        uint8_t* d = dst + k * n + b;
        const uint8_t* s = src + b * itemsize + k;
        for (size_t i = b; i < end; ++i, ++d, s += itemsize) *d = *s;
      }
    }
  } else {  // byte planes -> row-major values
    for (size_t b = 0; b < n; b += BLOCK) {
      size_t end = b + BLOCK < n ? b + BLOCK : n;
      for (size_t k = 0; k < itemsize; ++k) {
        const uint8_t* s = src + k * n + b;
        uint8_t* d = dst + b * itemsize + k;
        for (size_t i = b; i < end; ++i, ++s, d += itemsize) *d = *s;
      }
    }
  }
}

// ---------------------------------------------------------------------------
// spark murmur3 (x86_32) over variable-length byte strings
// ---------------------------------------------------------------------------

static inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}

static inline uint32_t mmh3_mix_k1(uint32_t k1) {
  k1 *= 0xcc9e2d51u;
  k1 = rotl32(k1, 15);
  return k1 * 0x1b873593u;
}

static inline uint32_t mmh3_mix_h1(uint32_t h1, uint32_t k1) {
  h1 ^= k1;
  h1 = rotl32(h1, 13);
  return h1 * 5 + 0xe6546b64u;
}

static inline uint32_t mmh3_fmix(uint32_t h1, uint32_t len) {
  h1 ^= len;
  h1 ^= h1 >> 16;
  h1 *= 0x85ebca6bu;
  h1 ^= h1 >> 13;
  h1 *= 0xc2b2ae35u;
  h1 ^= h1 >> 16;
  return h1;
}

// spark hashUnsafeBytes: 4-byte LE words, then each tail byte SIGN-EXTENDED
// through a full mix round.
EXPORT void bt_murmur3_bytes(const int64_t* offsets, const uint8_t* data,
                             const uint32_t* seeds, uint32_t* out, size_t n) {
  for (size_t i = 0; i < n; ++i) {
    const uint8_t* p = data + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    int64_t aligned = len & ~int64_t(3);
    uint32_t h1 = seeds[i];
    for (int64_t j = 0; j < aligned; j += 4) {
      uint32_t k;
      std::memcpy(&k, p + j, 4);  // little-endian host
      h1 = mmh3_mix_h1(h1, mmh3_mix_k1(k));
    }
    for (int64_t j = aligned; j < len; ++j) {
      int32_t b = static_cast<int8_t>(p[j]);  // signed byte
      h1 = mmh3_mix_h1(h1, mmh3_mix_k1(static_cast<uint32_t>(b)));
    }
    out[i] = mmh3_fmix(h1, static_cast<uint32_t>(len));
  }
}

// the same hash of dictionary entries BY CODE: row i hashes the bytes of
// entry codes[i], seeded by seeds[i]; a row that is not valid (valid may be
// null: all are) keeps its seed. No string is materialized a row.
EXPORT void bt_murmur3_codes(const int32_t* codes, const uint8_t* valid,
                             const int64_t* offsets, const uint8_t* data,
                             const uint32_t* seeds, uint32_t* out, size_t n) {
  for (size_t i = 0; i < n; ++i) {
    if (valid && !valid[i]) {
      out[i] = seeds[i];
      continue;
    }
    int64_t at = offsets[codes[i]];
    const uint8_t* p = data + at;
    int64_t len = offsets[codes[i] + 1] - at;
    int64_t aligned = len & ~int64_t(3);
    uint32_t h1 = seeds[i];
    for (int64_t j = 0; j < aligned; j += 4) {
      uint32_t k;
      std::memcpy(&k, p + j, 4);
      h1 = mmh3_mix_h1(h1, mmh3_mix_k1(k));
    }
    for (int64_t j = aligned; j < len; ++j) {
      int32_t b = static_cast<int8_t>(p[j]);
      h1 = mmh3_mix_h1(h1, mmh3_mix_k1(static_cast<uint32_t>(b)));
    }
    out[i] = mmh3_fmix(h1, static_cast<uint32_t>(len));
  }
}

// ---------------------------------------------------------------------------
// xxhash64 over variable-length byte strings (spark XXH64)
// ---------------------------------------------------------------------------

static const uint64_t P1 = 0x9E3779B185EBCA87ull;
static const uint64_t P2 = 0xC2B2AE3D27D4EB4Full;
static const uint64_t P3 = 0x165667B19E3779F9ull;
static const uint64_t P4 = 0x85EBCA77C2B2AE63ull;
static const uint64_t P5 = 0x27D4EB2F165667C5ull;

static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

static inline uint64_t xxh_round(uint64_t acc, uint64_t k) {
  return rotl64(acc + k * P2, 31) * P1;
}

EXPORT void bt_xxh64_bytes(const int64_t* offsets, const uint8_t* data,
                           const uint64_t* seeds, uint64_t* out, size_t n) {
  for (size_t i = 0; i < n; ++i) {
    const uint8_t* p = data + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    uint64_t seed = seeds[i];
    const uint8_t* end = p + len;
    uint64_t h;
    if (len >= 32) {
      uint64_t v1 = seed + P1 + P2, v2 = seed + P2, v3 = seed, v4 = seed - P1;
      const uint8_t* limit = end - 32;
      do {
        uint64_t k;
        std::memcpy(&k, p, 8); v1 = xxh_round(v1, k);
        std::memcpy(&k, p + 8, 8); v2 = xxh_round(v2, k);
        std::memcpy(&k, p + 16, 8); v3 = xxh_round(v3, k);
        std::memcpy(&k, p + 24, 8); v4 = xxh_round(v4, k);
        p += 32;
      } while (p <= limit);
      h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
      h = (h ^ xxh_round(0, v1)) * P1 + P4;
      h = (h ^ xxh_round(0, v2)) * P1 + P4;
      h = (h ^ xxh_round(0, v3)) * P1 + P4;
      h = (h ^ xxh_round(0, v4)) * P1 + P4;
    } else {
      h = seed + P5;
    }
    h += static_cast<uint64_t>(len);
    while (p + 8 <= end) {
      uint64_t k;
      std::memcpy(&k, p, 8);
      h = rotl64(h ^ xxh_round(0, k), 27) * P1 + P4;
      p += 8;
    }
    if (p + 4 <= end) {
      uint32_t k;
      std::memcpy(&k, p, 4);
      h = rotl64(h ^ (uint64_t(k) * P1), 23) * P2 + P3;
      p += 4;
    }
    while (p < end) {
      h = rotl64(h ^ (uint64_t(*p) * P5), 11) * P1;
      ++p;
    }
    h = (h ^ (h >> 33)) * P2;
    h = (h ^ (h >> 29)) * P3;
    out[i] = h ^ (h >> 32);
  }
}

// ---------------------------------------------------------------------------
// zstd frame codec
// ---------------------------------------------------------------------------

EXPORT int64_t bt_zstd_compress_bound(int64_t src_len) {
#ifdef HAVE_ZSTD
  return static_cast<int64_t>(ZSTD_compressBound(static_cast<size_t>(src_len)));
#else
  return -1;
#endif
}

EXPORT int64_t bt_zstd_compress(const uint8_t* src, int64_t src_len,
                                uint8_t* dst, int64_t dst_cap, int level) {
#ifdef HAVE_ZSTD
  size_t r = ZSTD_compress(dst, static_cast<size_t>(dst_cap), src,
                           static_cast<size_t>(src_len), level);
  if (ZSTD_isError(r)) return -1;
  return static_cast<int64_t>(r);
#else
  (void)src; (void)src_len; (void)dst; (void)dst_cap; (void)level;
  return -1;
#endif
}

EXPORT int64_t bt_zstd_decompress(const uint8_t* src, int64_t src_len,
                                  uint8_t* dst, int64_t dst_cap) {
#ifdef HAVE_ZSTD
  size_t r = ZSTD_decompress(dst, static_cast<size_t>(dst_cap), src,
                             static_cast<size_t>(src_len));
  if (ZSTD_isError(r)) return -1;
  return static_cast<int64_t>(r);
#else
  (void)src; (void)src_len; (void)dst; (void)dst_cap;
  return -1;
#endif
}

// ---------------------------------------------------------------------------
// lz4 block codec (reference supports lz4 + zstd shuffle/spill codecs,
// common/ipc_compression.rs:34-260). The image ships liblz4.so.1 without
// headers, so the three stable-ABI entry points are declared here and
// resolved with dlopen at first use.
// ---------------------------------------------------------------------------

#include <dlfcn.h>

namespace {
typedef int (*lz4_bound_fn)(int);
typedef int (*lz4_compress_fn)(const char*, char*, int, int);
typedef int (*lz4_decompress_fn)(const char*, char*, int, int);

struct Lz4Api {
  lz4_bound_fn bound = nullptr;
  lz4_compress_fn compress = nullptr;
  lz4_decompress_fn decompress = nullptr;
  bool ok = false;
};

const Lz4Api& lz4_api() {
  static Lz4Api api = [] {
    Lz4Api a;
    void* h = dlopen("liblz4.so.1", RTLD_NOW | RTLD_GLOBAL);
    if (!h) h = dlopen("liblz4.so", RTLD_NOW | RTLD_GLOBAL);
    if (h) {
      a.bound = reinterpret_cast<lz4_bound_fn>(dlsym(h, "LZ4_compressBound"));
      a.compress = reinterpret_cast<lz4_compress_fn>(
          dlsym(h, "LZ4_compress_default"));
      a.decompress = reinterpret_cast<lz4_decompress_fn>(
          dlsym(h, "LZ4_decompress_safe"));
      a.ok = a.bound && a.compress && a.decompress;
    }
    return a;
  }();
  return api;
}
}  // namespace

EXPORT int bt_lz4_available() { return lz4_api().ok ? 1 : 0; }

EXPORT int64_t bt_lz4_compress_bound(int64_t src_len) {
  const Lz4Api& a = lz4_api();
  if (!a.ok || src_len > INT32_MAX) return -1;
  return a.bound(static_cast<int>(src_len));
}

EXPORT int64_t bt_lz4_compress(const uint8_t* src, int64_t src_len,
                               uint8_t* dst, int64_t dst_cap) {
  const Lz4Api& a = lz4_api();
  if (!a.ok || src_len > INT32_MAX || dst_cap > INT32_MAX) return -1;
  int r = a.compress(reinterpret_cast<const char*>(src),
                     reinterpret_cast<char*>(dst),
                     static_cast<int>(src_len), static_cast<int>(dst_cap));
  return r > 0 ? r : -1;
}

EXPORT int64_t bt_lz4_decompress(const uint8_t* src, int64_t src_len,
                                 uint8_t* dst, int64_t dst_cap) {
  const Lz4Api& a = lz4_api();
  if (!a.ok || src_len > INT32_MAX || dst_cap > INT32_MAX) return -1;
  int r = a.decompress(reinterpret_cast<const char*>(src),
                       reinterpret_cast<char*>(dst),
                       static_cast<int>(src_len), static_cast<int>(dst_cap));
  return r >= 0 ? r : -1;
}

EXPORT int bt_version() { return 2; }

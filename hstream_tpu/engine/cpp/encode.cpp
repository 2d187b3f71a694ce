// Native columnar wire-encode kernels for the bit-packed transport
// (engine/transport.py). The Python planner decides encodings; these
// loops do the heavy per-element passes: streaming bit-pack, delta
// pack, bool pack, and decimal quantize+verify — each a single pass.
//
// Reference parallel: the reference's ingest hot path is native too
// (hstream-store cbits append/batch path, hs_writer.cpp); SURVEY §7
// calls for "C++ ingest, columnar staging" so the host never stalls
// the device. Build: engine/codec_native.py (g++ -O3, no deps).

#include <cstdint>
#include <cstring>
#include <cmath>
#include <vector>
#if defined(__SSE2__)
#include <emmintrin.h>
#endif

extern "C" {

// ---- streaming bit-pack: out words = (cap*bits+31)/32 + 1 ------------------

static inline void pack_stream(const uint64_t *u, int64_t n, int bits,
                               uint32_t *out, int64_t nw) {
    std::memset(out, 0, nw * sizeof(uint32_t));
    uint64_t acc = 0;
    int fill = 0;
    uint32_t *w = out;
    for (int64_t i = 0; i < n; ++i) {
        acc |= u[i] << fill;
        fill += bits;
        if (fill >= 32) {
            *w++ = (uint32_t)acc;
            acc >>= 32;
            fill -= 32;
        }
    }
    if (fill > 0) *w++ = (uint32_t)acc;
}

// pack (v[i] - base) at `bits` bits each; v int64
void enc_pack_i64(const int64_t *v, int64_t n, int64_t base, int bits,
                  uint32_t *out, int64_t nw) {
    std::memset(out, 0, nw * sizeof(uint32_t));
    uint64_t acc = 0;
    int fill = 0;
    uint32_t *w = out;
    for (int64_t i = 0; i < n; ++i) {
        acc |= (uint64_t)(v[i] - base) << fill;
        fill += bits;
        if (fill >= 32) { *w++ = (uint32_t)acc; acc >>= 32; fill -= 32; }
    }
    if (fill > 0) *w++ = (uint32_t)acc;
}

void enc_pack_i32(const int32_t *v, int64_t n, int64_t base, int bits,
                  uint32_t *out, int64_t nw) {
    std::memset(out, 0, nw * sizeof(uint32_t));
    uint64_t acc = 0;
    int fill = 0;
    uint32_t *w = out;
    for (int64_t i = 0; i < n; ++i) {
        acc |= (uint64_t)(int64_t)(v[i] - base) << fill;
        fill += bits;
        if (fill >= 32) { *w++ = (uint32_t)acc; acc >>= 32; fill -= 32; }
    }
    if (fill > 0) *w++ = (uint32_t)acc;
}

// pack first differences (d[0] = 0) of a nondecreasing int64 stream
void enc_pack_diff_i64(const int64_t *v, int64_t n, int bits,
                       uint32_t *out, int64_t nw) {
    std::memset(out, 0, nw * sizeof(uint32_t));
    uint64_t acc = 0;
    int fill = 0;
    uint32_t *w = out;
    int64_t prev = n > 0 ? v[0] : 0;
    for (int64_t i = 0; i < n; ++i) {
        acc |= (uint64_t)(v[i] - prev) << fill;
        prev = v[i];
        fill += bits;
        if (fill >= 32) { *w++ = (uint32_t)acc; acc >>= 32; fill -= 32; }
    }
    if (fill > 0) *w++ = (uint32_t)acc;
}

void enc_pack_bool(const uint8_t *v, int64_t n, uint32_t *out, int64_t nw) {
    std::memset(out, 0, nw * sizeof(uint32_t));
    for (int64_t i = 0; i < n; ++i)
        if (v[i]) out[i >> 5] |= (uint32_t)1 << (i & 31);
}

// ---- stats (single pass, no intermediate arrays) ---------------------------

void enc_minmax_i64(const int64_t *v, int64_t n, int64_t *out_min,
                    int64_t *out_max) {
    int64_t lo = n ? v[0] : 0, hi = n ? v[0] : 0;
    for (int64_t i = 1; i < n; ++i) {
        if (v[i] < lo) lo = v[i];
        if (v[i] > hi) hi = v[i];
    }
    *out_min = lo;
    *out_max = hi;
}

void enc_minmax_i32(const int32_t *v, int64_t n, int64_t *out_min,
                    int64_t *out_max) {
    int32_t lo = n ? v[0] : 0, hi = n ? v[0] : 0;
    for (int64_t i = 1; i < n; ++i) {
        if (v[i] < lo) lo = v[i];
        if (v[i] > hi) hi = v[i];
    }
    *out_min = lo;
    *out_max = hi;
}

// nondecreasing check + max first-difference (for delta planning)
// returns 1 if nondecreasing, 0 otherwise
int32_t enc_diff_stats_i64(const int64_t *v, int64_t n, int64_t *out_dmax) {
    int64_t dmax = 0;
    for (int64_t i = 1; i < n; ++i) {
        int64_t d = v[i] - v[i - 1];
        if (d < 0) { *out_dmax = 0; return 0; }
        if (d > dmax) dmax = d;
    }
    *out_dmax = dmax;
    return 1;
}

// ---- decimal quantize + bit-exact verify (one pass) ------------------------
//
// q[i] = rint(v[i] * scale); fails (returns 0) on |q| > max_q or when
// (float)q * inv_scale != v[i] (the exact device-decode round trip).
// On success fills q (int32) and min/max.
int32_t enc_quantize_f32(const float *v, int64_t n, float scale,
                         float inv_scale, int64_t max_q, int32_t *q_out,
                         int64_t *out_min, int64_t *out_max) {
    int64_t lo = INT64_MAX, hi = INT64_MIN;
    for (int64_t i = 0; i < n; ++i) {
        float qf = std::nearbyintf(v[i] * scale);
        if (!(std::fabs(qf) <= (float)max_q)) return 0;  // NaN/inf too
        int32_t q = (int32_t)qf;
        if ((float)q * inv_scale != v[i]) return 0;
        q_out[i] = q;
        if (q < lo) lo = q;
        if (q > hi) hi = q;
    }
    *out_min = n ? lo : 0;
    *out_max = n ? hi : 0;
    return 1;
}

// ---- string -> key id table (server/tasks.py key_encode) -------------------
//
// The task thread resolves a batch's whole string dictionary in ONE call
// (GIL released): open addressing over the keys' UTF-8 bytes, linear
// probing, load <= 1/2. Derived state: the executor's _key_rev stays the
// truth and engine/keytable.py rebuilds this from it. Keys arrive as one
// buffer, '\0' between entries (a key holding a '\0' never gets here);
// their bytes live in one arena, so a probe touches a slot and, on a
// hash match, the key.

struct KtSlot {
    uint32_t tag;   // the hash's high half; its low bits choose the slot
    uint32_t off;
    uint32_t len;
    int32_t kid;    // < 0: the slot is empty
};

struct KeyTable {
    std::vector<KtSlot> slots;
    std::vector<char> arena;
    std::vector<int64_t> starts;   // kt_split's scratch, kept across calls
    int64_t used = 0;
};

static const KtSlot KT_EMPTY = {0, 0, 0, -1};

static inline uint64_t kt_hash(const char *p, int64_t n) {
    uint64_t h = 0xcbf29ce484222325ULL ^ (uint64_t)n;
    while (n >= 8) {
        uint64_t w;
        std::memcpy(&w, p, 8);
        h = (h ^ w) * 0x9e3779b97f4a7c15ULL;
        h ^= h >> 29;
        p += 8;
        n -= 8;
    }
    uint64_t w = 0;
    std::memcpy(&w, p, (size_t)n);
    h = (h ^ w) * 0x9e3779b97f4a7c15ULL;
    h ^= h >> 32;
    h *= 0xd6e8feb86659fd93ULL;
    return h ^ (h >> 32);
}

static inline bool kt_eq(const char *a, const char *b, int64_t n) {
    for (; n >= 8; a += 8, b += 8, n -= 8) {
        uint64_t x, y;
        std::memcpy(&x, a, 8);
        std::memcpy(&y, b, 8);
        if (x != y) return false;
    }
    for (; n > 0; ++a, ++b, --n)
        if (*a != *b) return false;
    return true;
}

// the key's slot, or the empty slot where it would go
static inline KtSlot *kt_find(KeyTable *t, const char *p, int64_t n,
                              uint64_t h) {
    uint64_t mask = t->slots.size() - 1;
    uint32_t tag = (uint32_t)(h >> 32);
    for (uint64_t i = h & mask;; i = (i + 1) & mask) {
        KtSlot *s = &t->slots[i];
        if (s->kid < 0) return s;
        if (s->tag == tag && s->len == (uint64_t)n
            && kt_eq(t->arena.data() + s->off, p, n))
            return s;
    }
}

static void kt_grow(KeyTable *t) {
    std::vector<KtSlot> old(t->slots.size() * 2, KT_EMPTY);
    old.swap(t->slots);
    uint64_t mask = t->slots.size() - 1;
    for (const KtSlot &s : old) {
        if (s.kid < 0) continue;
        uint64_t i = kt_hash(t->arena.data() + s.off, s.len) & mask;
        while (t->slots[i].kid >= 0) i = (i + 1) & mask;
        t->slots[i] = s;
    }
}

void *kt_new() {
    KeyTable *t = new KeyTable();
    t->slots.assign(1024, KT_EMPTY);
    return t;
}

void kt_free(void *h) { delete (KeyTable *)h; }

int64_t kt_size(void *h) { return ((KeyTable *)h)->used; }

// Where each of buf's n keys starts: starts[i] .. starts[i + 1] - 1 is key
// i, its '\0' left out. False when buf does not hold n keys, '\0' between
// them (one separator more means a key carries a '\0' itself).
static bool kt_split(const char *buf, int64_t len, int64_t n,
                     std::vector<int64_t> &starts) {
    starts.resize((size_t)n + 1);
    int64_t k = 1, i = 0;
    starts[0] = 0;
#if defined(__SSE2__)
    const __m128i zero = _mm_setzero_si128();
    for (; i + 16 <= len; i += 16) {
        __m128i v = _mm_loadu_si128((const __m128i *)(buf + i));
        unsigned m = (unsigned)_mm_movemask_epi8(_mm_cmpeq_epi8(v, zero));
        for (; m; m &= m - 1) {
            if (k >= n) return false;
            starts[k++] = i + __builtin_ctz(m) + 1;
        }
    }
#endif
    for (; i < len; ++i) {
        if (buf[i] == 0) {
            if (k >= n) return false;
            starts[k++] = i + 1;
        }
    }
    starts[n] = len + 1;
    return k == n;
}

// out[i] = key id of the i-th key of buf, -1 where the table has none.
// Returns n, or -1 with out untouched when buf does not hold n keys.
// At 100 000 keys the slots and the arena are past the caches and a probe
// is two misses, so keys go in blocks: hash all and prefetch their slots,
// prefetch the keys the slots point at, then compare.
int64_t kt_resolve(void *h, const char *buf, int64_t len, int64_t n,
                   int32_t *out) {
    KeyTable *t = (KeyTable *)h;
    if (n == 0) return 0;
    std::vector<int64_t> &starts = t->starts;
    if (!kt_split(buf, len, n, starts)) return -1;
    const int64_t B = 32;
    uint64_t hs[B];
    const KtSlot *slots = t->slots.data();
    uint64_t mask = t->slots.size() - 1;
    for (int64_t i0 = 0; i0 < n; i0 += B) {
        int64_t nb = n - i0 < B ? n - i0 : B;
        const int64_t *st = &starts[i0];
        for (int64_t j = 0; j < nb; ++j) {
            hs[j] = kt_hash(buf + st[j], st[j + 1] - st[j] - 1);
            __builtin_prefetch(&slots[hs[j] & mask]);
        }
        for (int64_t j = 0; j < nb; ++j) {
            const KtSlot *s = &slots[hs[j] & mask];
            if (s->kid >= 0) __builtin_prefetch(t->arena.data() + s->off);
        }
        for (int64_t j = 0; j < nb; ++j)
            out[i0 + j] = kt_find(t, buf + st[j], st[j + 1] - st[j] - 1,
                                  hs[j])->kid;
    }
    return n;
}

// Key i of buf is inserted under kids[i]; a key already present keeps the
// id it has. Returns n, or -1 with the table untouched when buf does not
// hold n keys or the arena (4 GiB) is full.
int64_t kt_insert(void *h, const char *buf, int64_t len, int64_t n,
                  const int32_t *kids) {
    KeyTable *t = (KeyTable *)h;
    if (n == 0) return 0;
    std::vector<int64_t> &starts = t->starts;
    if (!kt_split(buf, len, n, starts)
        || t->arena.size() + (uint64_t)len > UINT32_MAX)
        return -1;
    for (int64_t i = 0; i < n; ++i) {
        const char *p = buf + starts[i];
        int64_t m = starts[i + 1] - starts[i] - 1;
        uint64_t hv = kt_hash(p, m);
        KtSlot *s = kt_find(t, p, m, hv);
        if (s->kid < 0) {
            *s = KtSlot{(uint32_t)(hv >> 32), (uint32_t)t->arena.size(),
                        (uint32_t)m, kids[i]};
            t->arena.insert(t->arena.end(), p, p + m);
            if (++t->used * 2 > (int64_t)t->slots.size()) kt_grow(t);
        }
    }
    return n;
}

}  // extern "C"

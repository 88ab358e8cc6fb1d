// Native ordered MVCC memtable (reference role: the in-proc engine that
// surrealdb/core/src/kvs/mem fills with its Rust MVCC btree).
//
// An ordered byte-keyspace where every key holds a short version chain;
// readers pin a snapshot version and resolve against it, writers commit
// batches that are validated for write-write conflicts against versions
// committed after their snapshot (optimistic, retryable — mirroring the
// Python engine in surrealdb_tpu/kvs/mem.py). Exported with a C ABI for the
// ctypes binding in surrealdb_tpu/native/__init__.py.
//
// All values returned to Python are copied into malloc'd buffers under the
// store mutex (sdb_buf_free releases them) — no interior pointers escape,
// so concurrent commits can never invalidate a buffer mid-read.
//
// The calls whose work under the mutex is bounded (a snapshot, its release,
// one key's read, a commit of a few keys) have a `_try` twin that takes the
// mutex only if it is free: the binding calls those while keeping the
// Python interpreter lock, and a twin that finds the mutex held returns
// SDB_BUSY (-1 where it returns an int), having touched nothing, so the
// caller falls back to the blocking call, which releases the interpreter.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <set>
#include <string>
#include <utility>
#include <vector>

namespace {

struct Entry {
    uint64_t ver;
    bool tombstone;
    std::string val;
};

struct Memtable {
    std::map<std::string, std::vector<Entry>> chains;
    uint64_t version = 0;
    std::multiset<uint64_t> active;
    std::mutex mu;
};

struct ScanIter {
    // materialized snapshot of the range (keeps iteration stable without
    // holding the store lock across Python callbacks)
    std::vector<std::pair<std::string, std::string>> items;
    size_t pos = 0;
};

const std::string* resolve(const std::vector<Entry>& chain, uint64_t snap) {
    const std::string* out = nullptr;
    for (const auto& e : chain) {
        if (e.ver > snap) break;
        out = e.tombstone ? nullptr : &e.val;
    }
    return out;
}

void prune(std::map<std::string, std::vector<Entry>>& chains,
           std::map<std::string, std::vector<Entry>>::iterator it,
           uint64_t min_active) {
    auto& chain = it->second;
    size_t keep_from = 0;
    for (size_t i = 0; i < chain.size(); i++) {
        if (chain[i].ver <= min_active)
            keep_from = i;
        else
            break;
    }
    if (keep_from) chain.erase(chain.begin(), chain.begin() + keep_from);
    if (chain.size() == 1 && chain[0].tombstone) chains.erase(it);
}

char* copy_out(const std::string& s) {
    char* buf = static_cast<char*>(std::malloc(s.size() ? s.size() : 1));
    std::memcpy(buf, s.data(), s.size());
    return buf;
}

constexpr uint64_t SDB_BUSY = UINT64_MAX;

using TryLock = std::unique_lock<std::mutex>;

uint64_t snapshot_locked(Memtable* m) {
    m->active.insert(m->version);
    return m->version;
}

void release_locked(Memtable* m, uint64_t snap) {
    auto it = m->active.find(snap);
    if (it != m->active.end()) m->active.erase(it);
}

int get_locked(Memtable* m, const char* key, int64_t klen, uint64_t snap,
               char** val, int64_t* vlen) {
    auto it = m->chains.find(std::string(key, klen));
    if (it == m->chains.end()) return 0;
    const std::string* v = resolve(it->second, snap);
    if (v == nullptr) return 0;
    *val = copy_out(*v);
    *vlen = static_cast<int64_t>(v->size());
    return 1;
}

// commit: interleaved (key, val) pairs; vlen < 0 marks a tombstone.
// Returns the new version, or 0 on write-write conflict (any written key
// has a committed version newer than `snap`). With release_snap, the
// committer's snapshot is removed from the active set under the SAME mutex
// hold, after validation — releasing before validating would let a
// concurrent delete prune a conflicting chain away and hide the conflict.
uint64_t commit_locked(Memtable* m, uint64_t snap, int64_t n,
                       const char** keys, const int64_t* klens,
                       const char** vals, const int64_t* vlens,
                       int release_snap) {
    bool conflict = false;
    for (int64_t i = 0; i < n && !conflict; i++) {
        auto it = m->chains.find(std::string(keys[i], klens[i]));
        if (it != m->chains.end() && !it->second.empty() &&
            it->second.back().ver > snap)
            conflict = true;
    }
    if (release_snap) release_locked(m, snap);
    if (conflict) return 0;
    uint64_t ver = ++m->version;
    uint64_t min_active = m->active.empty() ? ver : *m->active.begin();
    for (int64_t i = 0; i < n; i++) {
        std::string k(keys[i], klens[i]);
        bool tomb = vlens[i] < 0;
        auto it = m->chains.find(k);
        if (it == m->chains.end()) {
            if (tomb) continue;  // delete of a never-written key
            it = m->chains.emplace(std::move(k), std::vector<Entry>{}).first;
        }
        Entry e;
        e.ver = ver;
        e.tombstone = tomb;
        if (!tomb) e.val.assign(vals[i], vlens[i]);
        it->second.push_back(std::move(e));
        prune(m->chains, it, min_active);
    }
    return ver;
}

}  // namespace

extern "C" {

void* sdb_memtable_new() { return new Memtable(); }

void sdb_memtable_free(void* h) { delete static_cast<Memtable*>(h); }

void sdb_buf_free(char* p) { std::free(p); }

// snapshots ----------------------------------------------------------------

uint64_t sdb_snapshot(void* h) {
    auto* m = static_cast<Memtable*>(h);
    std::lock_guard<std::mutex> lock(m->mu);
    return snapshot_locked(m);
}

uint64_t sdb_snapshot_try(void* h) {
    auto* m = static_cast<Memtable*>(h);
    TryLock lock(m->mu, std::try_to_lock);
    if (!lock.owns_lock()) return SDB_BUSY;
    return snapshot_locked(m);
}

void sdb_snapshot_release(void* h, uint64_t snap) {
    auto* m = static_cast<Memtable*>(h);
    std::lock_guard<std::mutex> lock(m->mu);
    release_locked(m, snap);
}

int sdb_snapshot_release_try(void* h, uint64_t snap) {
    auto* m = static_cast<Memtable*>(h);
    TryLock lock(m->mu, std::try_to_lock);
    if (!lock.owns_lock()) return -1;
    release_locked(m, snap);
    return 0;
}

// reads --------------------------------------------------------------------

int sdb_get_at(void* h, const char* key, int64_t klen, uint64_t snap,
               char** val, int64_t* vlen) {
    auto* m = static_cast<Memtable*>(h);
    std::lock_guard<std::mutex> lock(m->mu);
    return get_locked(m, key, klen, snap, val, vlen);
}

int sdb_get_at_try(void* h, const char* key, int64_t klen, uint64_t snap,
                   char** val, int64_t* vlen) {
    auto* m = static_cast<Memtable*>(h);
    TryLock lock(m->mu, std::try_to_lock);
    if (!lock.owns_lock()) return -1;
    return get_locked(m, key, klen, snap, val, vlen);
}

int64_t sdb_len(void* h) {
    auto* m = static_cast<Memtable*>(h);
    std::lock_guard<std::mutex> lock(m->mu);
    int64_t n = 0;
    for (auto& kv : m->chains)
        if (!kv.second.empty() && !kv.second.back().tombstone) n++;
    return n;
}

// writes (commit_locked above) ---------------------------------------------

uint64_t sdb_commit_batch(void* h, uint64_t snap, int64_t n,
                          const char** keys, const int64_t* klens,
                          const char** vals, const int64_t* vlens,
                          int release_snap) {
    auto* m = static_cast<Memtable*>(h);
    std::lock_guard<std::mutex> lock(m->mu);
    return commit_locked(m, snap, n, keys, klens, vals, vlens, release_snap);
}

uint64_t sdb_commit_batch_try(void* h, uint64_t snap, int64_t n,
                              const char** keys, const int64_t* klens,
                              const char** vals, const int64_t* vlens,
                              int release_snap) {
    auto* m = static_cast<Memtable*>(h);
    TryLock lock(m->mu, std::try_to_lock);
    if (!lock.owns_lock()) return SDB_BUSY;
    return commit_locked(m, snap, n, keys, klens, vals, vlens, release_snap);
}

// range scans --------------------------------------------------------------

void* sdb_scan_new_at(void* h, const char* beg, int64_t blen, const char* end,
                      int64_t elen, uint64_t snap, int64_t limit,
                      int reverse) {
    auto* m = static_cast<Memtable*>(h);
    auto* it = new ScanIter();
    std::string kb(beg, blen), ke(end, elen);
    std::lock_guard<std::mutex> lock(m->mu);
    auto lo = m->chains.lower_bound(kb);
    auto hi = m->chains.lower_bound(ke);
    if (!reverse) {
        for (auto cur = lo; cur != hi; ++cur) {
            const std::string* v = resolve(cur->second, snap);
            if (v == nullptr) continue;
            it->items.emplace_back(cur->first, *v);
            if (limit >= 0 &&
                static_cast<int64_t>(it->items.size()) >= limit)
                break;
        }
    } else {
        for (auto cur = hi; cur != lo;) {
            --cur;
            const std::string* v = resolve(cur->second, snap);
            if (v == nullptr) continue;
            it->items.emplace_back(cur->first, *v);
            if (limit >= 0 &&
                static_cast<int64_t>(it->items.size()) >= limit)
                break;
        }
    }
    return it;
}

void sdb_scan_free(void* hit) { delete static_cast<ScanIter*>(hit); }

// Batched drain: pack up to max_items [u32 klen][u32 vlen][key][val]
// frames into buf (cap bytes). Returns the number of items packed and
// writes the used byte count — one FFI crossing per few hundred rows
// instead of one per row. A packed item's strings are freed here, a batch
// at a time, so freeing a drained iterator is cheap (the binding frees it
// while keeping the interpreter).
int64_t sdb_scan_batch(void* hit, char* buf, int64_t cap,
                       int64_t max_items, int64_t* used) {
    auto* it = static_cast<ScanIter*>(hit);
    int64_t count = 0;
    int64_t off = 0;
    while (count < max_items && it->pos < it->items.size()) {
        auto& kv = it->items[it->pos];
        int64_t need = 8 + static_cast<int64_t>(kv.first.size()) +
                       static_cast<int64_t>(kv.second.size());
        if (off + need > cap) {
            if (count == 0) return -1;  // buffer too small for one item
            break;
        }
        uint32_t kl = static_cast<uint32_t>(kv.first.size());
        uint32_t vl = static_cast<uint32_t>(kv.second.size());
        std::memcpy(buf + off, &kl, 4);
        std::memcpy(buf + off + 4, &vl, 4);
        std::memcpy(buf + off + 8, kv.first.data(), kl);
        std::memcpy(buf + off + 8 + kl, kv.second.data(), vl);
        std::string().swap(kv.first);
        std::string().swap(kv.second);
        off += need;
        it->pos++;
        count++;
    }
    *used = off;
    return count;
}

int64_t sdb_count_range_at(void* h, const char* beg, int64_t blen,
                           const char* end, int64_t elen, uint64_t snap) {
    auto* m = static_cast<Memtable*>(h);
    std::string kb(beg, blen), ke(end, elen);
    std::lock_guard<std::mutex> lock(m->mu);
    auto lo = m->chains.lower_bound(kb);
    auto hi = m->chains.lower_bound(ke);
    int64_t n = 0;
    for (auto cur = lo; cur != hi; ++cur)
        if (resolve(cur->second, snap) != nullptr) n++;
    return n;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Columnar field extraction (reference role: the compiled scan kernels in
// core/src/exec/operators/scan — decode rows natively instead of in the
// host language). Scans [beg,end) at a snapshot, CBOR-decodes each value
// just enough to pull ONE top-level field as a fixed-dim float vector, and
// returns a packed float32 matrix plus the matching key suffixes. Rows
// whose field is missing/ragged/non-numeric are returned as raw key frames
// for the interpreter fallback.

namespace {

// minimal CBOR walker for the wire.py subset (definite lengths only)
struct CborCur {
    const uint8_t* p;
    const uint8_t* end;
    bool ok = true;

    uint64_t head(uint8_t* major) {
        if (p >= end) { ok = false; return 0; }
        uint8_t ib = *p++;
        *major = ib >> 5;
        uint8_t info = ib & 0x1f;
        if (info < 24) return info;
        int n = info == 24 ? 1 : info == 25 ? 2 : info == 26 ? 4
                : info == 27 ? 8 : -1;
        if (n < 0 || p + n > end) { ok = false; return 0; }
        uint64_t v = 0;
        for (int i = 0; i < n; i++) v = (v << 8) | *p++;
        return v;
    }

    void skip() {
        uint8_t major;
        uint64_t arg = head(&major);
        if (!ok) return;
        switch (major) {
            case 0: case 1: return;                 // ints
            case 2: case 3:                          // bytes / text
                if (p + arg > end) { ok = false; return; }
                p += arg;
                return;
            case 4:                                  // array
                for (uint64_t i = 0; i < arg && ok; i++) skip();
                return;
            case 5:                                  // map
                for (uint64_t i = 0; i < arg && ok; i++) { skip(); skip(); }
                return;
            case 6:                                  // tag: one item
                skip();
                return;
            case 7:
                // simple values carry no payload beyond the head except
                // f16/f32/f64 which head() already consumed as the arg
                return;
            default:
                ok = false;
        }
    }

    // floats/ints decode to double; everything else fails
    bool number(double* out) {
        if (p >= end) return false;
        uint8_t ib = *p;
        uint8_t major = ib >> 5;
        if (major == 0) { uint8_t m; *out = (double)head(&m); return ok; }
        if (major == 1) {
            uint8_t m;
            uint64_t v = head(&m);
            *out = -1.0 - (double)v;
            return ok;
        }
        if (ib == 0xfb) {                            // float64
            if (p + 9 > end) return false;
            p++;
            uint64_t bits = 0;
            for (int i = 0; i < 8; i++) bits = (bits << 8) | *p++;
            double d;
            std::memcpy(&d, &bits, 8);
            *out = d;
            return true;
        }
        if (ib == 0xfa) {                            // float32
            if (p + 5 > end) return false;
            p++;
            uint32_t bits = 0;
            for (int i = 0; i < 4; i++) bits = (bits << 8) | *p++;
            float f;
            std::memcpy(&f, &bits, 4);
            *out = (double)f;
            return true;
        }
        return false;
    }
};

// Extract doc[fname] as a dim-length numeric array into out[0..dim).
// val must be the serialized record payload ('\x01' + CBOR map).
bool extract_field_vec(const std::string& val, const char* fname,
                       int64_t fnlen, int64_t dim, float* out) {
    if (val.size() < 2 || (uint8_t)val[0] != 0x01) return false;
    CborCur c{reinterpret_cast<const uint8_t*>(val.data()) + 1,
              reinterpret_cast<const uint8_t*>(val.data()) + val.size()};
    uint8_t major;
    uint64_t npairs = c.head(&major);
    if (!c.ok || major != 5) return false;
    for (uint64_t i = 0; i < npairs && c.ok; i++) {
        uint8_t km;
        uint64_t klen = c.head(&km);
        if (!c.ok || km != 3) return false;  // keys are text strings
        const uint8_t* kp = c.p;
        if (c.p + klen > c.end) return false;
        c.p += klen;
        bool match = (int64_t)klen == fnlen &&
                     std::memcmp(kp, fname, fnlen) == 0;
        if (!match) {
            c.skip();
            continue;
        }
        uint8_t vm;
        uint64_t alen = c.head(&vm);
        if (!c.ok || vm != 4 || (int64_t)alen != dim) return false;
        for (int64_t j = 0; j < dim; j++) {
            double d;
            if (!c.number(&d)) return false;
            out[j] = (float)d;
        }
        return true;
    }
    return false;
}

}  // namespace

extern "C" {

// Returns the number of rows extracted into `mat` (row-major rows*dim
// float32) with their key suffixes (bytes after `skip_prefix`) packed as
// [u32 len][bytes] frames into keybuf. Rows that fail extraction pack
// their key suffixes into badbuf the same way (badcount written).
// A return of -1 means a buffer was too small — caller grows and retries.
int64_t sdb_scan_extract_f32(void* h, const char* beg, int64_t blen,
                             const char* end, int64_t elen, uint64_t snap,
                             const char* fname, int64_t fnlen, int64_t dim,
                             int64_t skip_prefix,
                             float* mat, int64_t max_rows,
                             char* keybuf, int64_t keycap, int64_t* keyused,
                             char* badbuf, int64_t badcap, int64_t* badused,
                             int64_t* badcount) {
    auto* m = static_cast<Memtable*>(h);
    std::string kb(beg, blen), ke(end, elen);
    std::lock_guard<std::mutex> lock(m->mu);
    auto lo = m->chains.lower_bound(kb);
    auto hi = m->chains.lower_bound(ke);
    int64_t rows = 0;
    int64_t koff = 0, boff = 0, bad = 0;
    for (auto cur = lo; cur != hi; ++cur) {
        const std::string* v = resolve(cur->second, snap);
        if (v == nullptr) continue;
        const std::string& key = cur->first;
        int64_t sfx = (int64_t)key.size() - skip_prefix;
        if (sfx < 0) sfx = 0;
        const char* sp = key.data() + (key.size() - sfx);
        if (rows >= max_rows) return -2;  // matrix full: caller grows
        if (extract_field_vec(*v, fname, fnlen, dim, mat + rows * dim)) {
            int64_t need = 4 + sfx;
            if (koff + need > keycap) return -1;
            uint32_t sl = (uint32_t)sfx;
            std::memcpy(keybuf + koff, &sl, 4);
            std::memcpy(keybuf + koff + 4, sp, sfx);
            koff += need;
            rows++;
        } else {
            int64_t need = 4 + sfx;
            if (boff + need > badcap) return -1;
            uint32_t sl = (uint32_t)sfx;
            std::memcpy(badbuf + boff, &sl, 4);
            std::memcpy(badbuf + boff + 4, sp, sfx);
            boff += need;
            bad++;
        }
    }
    *keyused = koff;
    *badused = boff;
    *badcount = bad;
    return rows;
}

}  // extern "C"

// Native host-path operations for the frame pipeline: string interning and
// pre-pool admission.
//
// Why: the consumer's host path is the end-to-end throughput ceiling once
// the device and the fetch overlap (engine/pipeline.py). Profiling the
// 262K-order frame shape shows ~2.6 us/order spent in two pure-Python
// loops: per-order (symbol, uuid, oid) tuple construction + set ops for
// pre-pool admission (the reference's ExistsPrePool/DeletePrePool pair,
// engine.go:58-62), and per-order oid dict interning. std::unordered_*
// (node mallocs, chained buckets) still costs ~0.5-0.8 us/op at this
// shape, so both tables here are open-addressing flat tables (power-of-2
// capacity, linear probing, 64-bit FNV-1a-mix hashes) over append-only
// byte arenas — one memcpy and ~2 cache lines per op, no per-entry
// allocation.
//
// Two objects behind a C ABI (ctypes, no pybind11 in this image):
//
//   Interner  — append-only string -> dense id table (ids from 1; 0 is
//               the reserved "none" of the device arrays). Batch intern
//               over a numpy 'S'-dtype column (fixed width, NUL-padded),
//               padded gather for the event-frame id tables, len-prefixed
//               export/import for snapshots.
//   PrePool   — the marker set (engine/prepool.py contract), keys
//               composed as "symbol\x1Fuuid\x1Foid" ('\x1F' = ASCII unit
//               separator; the ids round-trip the reference's JSON wire
//               contract and never contain control bytes). One fused call
//               admits a whole decoded ORDER frame: compose key, pop
//               marker, emit keep/existed masks — mode 1 marks (the
//               gateway side, nodepool.go:14-16), mode 2 restores a
//               consumed selection (failed-batch rollback). Erasure uses
//               tombstones; rehash compacts live keys into a fresh arena,
//               so long-running churn (mark+consume per order) does not
//               grow memory unboundedly.
//
// Thread-safety: PrePool ops take a mutex (the gateway's gRPC threads mark
// while the consumer admits). The Interner is single-consumer-thread by
// design (documented in engine/host.py) and unlocked.

#include <cstdint>
#include <cstring>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

namespace {

inline uint64_t hash_bytes(const char* p, size_t n) {
  uint64_t h = 1469598103934665603ull;  // FNV-1a 64
  for (size_t i = 0; i < n; ++i) {
    h ^= static_cast<unsigned char>(p[i]);
    h *= 1099511628211ull;
  }
  // Final avalanche (splitmix64 tail): FNV alone clusters low bits.
  h ^= h >> 30;
  h *= 0xbf58476d1ce4e5b9ull;
  h ^= h >> 27;
  h *= 0x94d049bb133111ebull;
  h ^= h >> 31;
  return h ? h : 1;  // 0 is the empty-slot sentinel
}

struct Arena {
  std::vector<std::unique_ptr<char[]>> chunks;
  size_t cap = 0, used = 0;

  const char* put(const char* p, size_t n) {
    if (used + n > cap) {
      cap = n > (1u << 20) ? n : (1u << 20);
      chunks.emplace_back(new char[cap]);
      used = 0;
    }
    char* dst = chunks.back().get() + used;
    std::memcpy(dst, p, n);
    used += n;
    return dst;
  }
};

struct StrRef {
  const char* p;
  uint32_t len;
};

inline std::pair<const char*, int64_t> trim_padded(const char* p,
                                                   int64_t width) {
  int64_t len = width;
  while (len > 0 && p[len - 1] == '\0') --len;
  return {p, len};
}

// ---------------------------------------------------------------- Interner
struct Interner {
  // Interleaved {hash, id} slots: one prefetched cache line serves both
  // the hash compare and the id deref (split arrays cost two misses).
  struct Slot {
    uint64_t h;  // 0 = empty
    int64_t id;
  };
  std::vector<Slot> slots;
  size_t mask = 0, count = 0;
  Arena arena;
  std::vector<StrRef> strs;  // id-1 -> bytes
  int64_t max_len = 0;

  Interner() { rehash(1 << 12); }

  void rehash(size_t new_cap) {
    std::vector<Slot> s2(new_cap, Slot{0, 0});
    size_t m2 = new_cap - 1;
    for (size_t i = 0; i <= mask && !slots.empty(); ++i) {
      if (!slots[i].h) continue;
      size_t j = slots[i].h & m2;
      while (s2[j].h) j = (j + 1) & m2;
      s2[j] = slots[i];
    }
    slots.swap(s2);
    mask = m2;
  }

  int64_t intern(const char* p, size_t n) {
    return intern_hashed(p, n, hash_bytes(p, n));
  }

  int64_t intern_hashed(const char* p, size_t n, uint64_t h) {
    size_t i = h & mask;
    while (slots[i].h) {
      if (slots[i].h == h) {
        const StrRef& s = strs[static_cast<size_t>(slots[i].id - 1)];
        if (s.len == n && std::memcmp(s.p, p, n) == 0) return slots[i].id;
      }
      i = (i + 1) & mask;
    }
    const char* stored = arena.put(p, n);
    strs.push_back({stored, static_cast<uint32_t>(n)});
    int64_t id = static_cast<int64_t>(strs.size());
    slots[i] = {h, id};
    if (static_cast<int64_t>(n) > max_len) max_len = static_cast<int64_t>(n);
    if (++count * 4 > (mask + 1) * 3) rehash((mask + 1) * 2);
    return id;
  }

  int64_t get(const char* p, size_t n) const {
    uint64_t h = hash_bytes(p, n);
    size_t i = h & mask;
    while (slots[i].h) {
      if (slots[i].h == h) {
        const StrRef& s = strs[static_cast<size_t>(slots[i].id - 1)];
        if (s.len == n && std::memcmp(s.p, p, n) == 0) return slots[i].id;
      }
      i = (i + 1) & mask;
    }
    return 0;
  }
};

// ---------------------------------------------------------------- PrePool
struct PrePool {
  // Interleaved {hash, ref} slots (one prefetched line serves both).
  // ref: 0 = empty, -1 = tombstone, else index+1 into keys.
  struct Slot {
    uint64_t h;
    int64_t ref;
  };
  std::vector<Slot> slots;
  size_t mask = 0, live = 0, tombs = 0;
  Arena arena;
  std::vector<StrRef> keys;       // append-only; dead entries len = 0
  std::vector<uint8_t> key_live;  // parallel liveness for rehash compaction
  std::mutex mu;

  PrePool() { rehash(1 << 12); }

  void rehash(size_t new_cap) {
    // Compact: copy only LIVE keys into a fresh arena so churn (mark +
    // consume per order) cannot grow memory without bound.
    Arena a2;
    std::vector<StrRef> k2;
    std::vector<uint8_t> l2;
    std::vector<Slot> s2(new_cap, Slot{0, 0});
    size_t m2 = new_cap - 1;
    k2.reserve(live);
    for (size_t i = 0; i <= mask && !slots.empty(); ++i) {
      if (!slots[i].h || slots[i].ref <= 0) continue;
      const StrRef& s = keys[static_cast<size_t>(slots[i].ref - 1)];
      const char* stored = a2.put(s.p, s.len);
      k2.push_back({stored, s.len});
      l2.push_back(1);
      size_t j = slots[i].h & m2;
      while (s2[j].h) j = (j + 1) & m2;
      s2[j] = {slots[i].h, static_cast<int64_t>(k2.size())};
    }
    slots.swap(s2);
    arena = std::move(a2);
    keys.swap(k2);
    key_live.swap(l2);
    mask = m2;
    tombs = 0;
  }

  void maybe_grow() {
    if ((live + tombs) * 4 > (mask + 1) * 3)
      rehash(live * 4 > (mask + 1) ? (mask + 1) * 2 : mask + 1);
  }

  // returns slot index holding the key, or SIZE_MAX.
  size_t find(const char* p, size_t n, uint64_t h) const {
    size_t i = h & mask;
    while (slots[i].h || slots[i].ref == -1) {
      if (slots[i].h == h && slots[i].ref > 0) {
        const StrRef& s = keys[static_cast<size_t>(slots[i].ref - 1)];
        if (s.len == n && std::memcmp(s.p, p, n) == 0) return i;
      }
      i = (i + 1) & mask;
    }
    return SIZE_MAX;
  }

  bool insert(const char* p, size_t n) {
    return insert_hashed(p, n, hash_bytes(p, n));
  }

  bool insert_hashed(const char* p, size_t n, uint64_t h) {
    if (find(p, n, h) != SIZE_MAX) return false;
    size_t i = h & mask;
    while (slots[i].h && slots[i].ref != -1) i = (i + 1) & mask;
    if (slots[i].ref == -1) --tombs;
    const char* stored = arena.put(p, n);
    keys.push_back({stored, static_cast<uint32_t>(n)});
    key_live.push_back(1);
    slots[i] = {h, static_cast<int64_t>(keys.size())};
    ++live;
    maybe_grow();
    return true;
  }

  bool erase(const char* p, size_t n) {
    return erase_hashed(p, n, hash_bytes(p, n));
  }

  bool erase_hashed(const char* p, size_t n, uint64_t h) {
    size_t i = find(p, n, h);
    if (i == SIZE_MAX) return false;
    key_live[static_cast<size_t>(slots[i].ref - 1)] = 0;
    slots[i] = {0, -1};  // tombstone keeps probe chains intact
    --live;
    ++tombs;
    if (tombs * 2 > mask + 1) rehash(mask + 1);
    return true;
  }

  bool contains(const char* p, size_t n) {
    return find(p, n, hash_bytes(p, n)) != SIZE_MAX;
  }
};

constexpr char kSep = '\x1F';

struct StrList {
  const char* data;
  const int64_t* offs;
};

}  // namespace

extern "C" {

// ---------------------------------------------------------------- Interner
void* gi_new() { return new Interner(); }
void gi_free(void* h) { delete static_cast<Interner*>(h); }

int64_t gi_len(void* h) {
  return static_cast<int64_t>(static_cast<Interner*>(h)->strs.size());
}

int64_t gi_max_len(void* h) { return static_cast<Interner*>(h)->max_len; }

int64_t gi_intern_one(void* h, const char* p, int64_t len) {
  return static_cast<Interner*>(h)->intern(p, static_cast<size_t>(len));
}

int64_t gi_get(void* h, const char* p, int64_t len) {
  return static_cast<Interner*>(h)->get(p, static_cast<size_t>(len));
}

void gi_intern_batch(void* h, const char* data, int64_t n, int64_t width,
                     int64_t* out_ids) {
  auto& in = *static_cast<Interner*>(h);
  // Ensure no rehash mid-batch (so prefetched slots stay valid) and
  // block-prefetch: hash a block, prefetch its slots, then probe — the
  // probes are independent DRAM misses, so overlapping them across the
  // block hides most of the latency.
  if ((in.count + static_cast<size_t>(n)) * 4 > (in.mask + 1) * 3) {
    size_t cap = in.mask + 1;
    while ((in.count + static_cast<size_t>(n)) * 4 > cap * 3) cap *= 2;
    in.rehash(cap);
  }
  constexpr int64_t B = 32;
  uint64_t hs[B];
  for (int64_t base = 0; base < n; base += B) {
    int64_t m = n - base < B ? n - base : B;
    for (int64_t j = 0; j < m; ++j) {
      auto [p, len] = trim_padded(data + (base + j) * width, width);
      hs[j] = hash_bytes(p, static_cast<size_t>(len));
      __builtin_prefetch(&in.slots[hs[j] & in.mask]);
    }
    for (int64_t j = 0; j < m; ++j) {
      auto [p, len] = trim_padded(data + (base + j) * width, width);
      out_ids[base + j] =
          in.intern_hashed(p, static_cast<size_t>(len), hs[j]);
    }
  }
}

int64_t gi_lookup(void* h, int64_t id, char* out, int64_t cap) {
  auto& in = *static_cast<Interner*>(h);
  if (id == 0) return 0;
  if (id < 0 || id > static_cast<int64_t>(in.strs.size())) return -1;
  const StrRef& s = in.strs[static_cast<size_t>(id - 1)];
  if (static_cast<int64_t>(s.len) > cap) return -1;
  std::memcpy(out, s.p, s.len);
  return static_cast<int64_t>(s.len);
}

// Max string length over just the requested ids (so gathered id tables
// pad to the BATCH max, not the process-lifetime max — one long id must
// not inflate every later frame). Returns -1 on an out-of-range id.
int64_t gi_gather_width(void* h, const int64_t* ids, int64_t n) {
  auto& in = *static_cast<Interner*>(h);
  int64_t sz = static_cast<int64_t>(in.strs.size());
  int64_t w = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t id = ids[i];
    if (id == 0) continue;
    if (id < 0 || id > sz) return -1;
    int64_t len =
        static_cast<int64_t>(in.strs[static_cast<size_t>(id - 1)].len);
    if (len > w) w = len;
  }
  return w;
}

int64_t gi_gather(void* h, const int64_t* ids, int64_t n, char* out,
                  int64_t width) {
  auto& in = *static_cast<Interner*>(h);
  int64_t sz = static_cast<int64_t>(in.strs.size());
  for (int64_t i = 0; i < n; ++i) {
    char* cell = out + i * width;
    std::memset(cell, 0, static_cast<size_t>(width));
    int64_t id = ids[i];
    if (id == 0) continue;
    if (id < 0 || id > sz) return -1;
    const StrRef& s = in.strs[static_cast<size_t>(id - 1)];
    if (static_cast<int64_t>(s.len) > width) return -1;
    std::memcpy(cell, s.p, s.len);
  }
  return 0;
}

int64_t gi_export(void* h, char* out, int64_t cap) {
  auto& in = *static_cast<Interner*>(h);
  int64_t need = 0;
  for (const auto& s : in.strs) need += 4 + static_cast<int64_t>(s.len);
  if (cap < need) return need;
  char* p = out;
  for (const auto& s : in.strs) {
    uint32_t len = s.len;
    std::memcpy(p, &len, 4);
    p += 4;
    std::memcpy(p, s.p, s.len);
    p += s.len;
  }
  return need;
}

int64_t gi_import(void* h, const char* data, int64_t nbytes, int64_t n) {
  auto& in = *static_cast<Interner*>(h);
  if (!in.strs.empty()) return -1;
  const char* p = data;
  const char* end = data + nbytes;
  for (int64_t i = 0; i < n; ++i) {
    if (p + 4 > end) return -1;
    uint32_t len;
    std::memcpy(&len, p, 4);
    p += 4;
    if (p + len > end) return -1;
    in.intern(p, len);
    p += len;
  }
  return 0;
}

// ---------------------------------------------------------------- PrePool
void* gp_new() { return new PrePool(); }
void gp_free(void* h) { delete static_cast<PrePool*>(h); }

int64_t gp_len(void* h) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  return static_cast<int64_t>(pp.live);
}

int64_t gp_add(void* h, const char* p, int64_t len) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  return pp.insert(p, static_cast<size_t>(len)) ? 1 : 0;
}

int64_t gp_discard(void* h, const char* p, int64_t len) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  return pp.erase(p, static_cast<size_t>(len)) ? 1 : 0;
}

int64_t gp_contains(void* h, const char* p, int64_t len) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  return pp.contains(p, static_cast<size_t>(len)) ? 1 : 0;
}

void gp_clear(void* h) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  pp.slots.assign(pp.mask + 1, PrePool::Slot{0, 0});
  pp.arena = Arena();
  pp.keys.clear();
  pp.key_live.clear();
  pp.live = pp.tombs = 0;
}

int64_t gp_dump(void* h, char* out, int64_t cap) {
  auto& pp = *static_cast<PrePool*>(h);
  std::lock_guard<std::mutex> g(pp.mu);
  int64_t need = 0;
  for (size_t k = 0; k < pp.keys.size(); ++k)
    if (pp.key_live[k]) need += 4 + static_cast<int64_t>(pp.keys[k].len);
  if (cap < need) return need;
  char* p = out;
  for (size_t k = 0; k < pp.keys.size(); ++k) {
    if (!pp.key_live[k]) continue;
    uint32_t len = pp.keys[k].len;
    std::memcpy(p, &len, 4);
    p += 4;
    std::memcpy(p, pp.keys[k].p, len);
    p += len;
  }
  return need;
}

// The fused frame pass — see engine/prepool.py NativePrePool._frame for
// the calling convention. mode 0 = consume (admission, engine.go:58-62 +
// 88-90), mode 1 = mark ADDs (gateway, main.go:42-45), mode 2 = restore
// rows selected by `existed` (failed-batch rollback).
int64_t gp_frame(void* h, int64_t n, const uint8_t* action,
                 const char* sym_data, const int64_t* sym_offs,
                 const uint32_t* sym_idx, const char* uuid_data,
                 const int64_t* uuid_offs, const uint32_t* uuid_idx,
                 const char* oids, int64_t oid_width, int64_t add_val,
                 int64_t del_val, uint8_t* keep, uint8_t* existed,
                 int64_t mode) {
  auto& pp = *static_cast<PrePool*>(h);
  StrList syms{sym_data, sym_offs};
  StrList uuids{uuid_data, uuid_offs};
  std::lock_guard<std::mutex> g(pp.mu);
  if (mode != 0) {
    // Insert modes can rehash; presize once up front.
    size_t want = pp.live + pp.tombs + static_cast<size_t>(n);
    if (want * 4 > (pp.mask + 1) * 3) {
      size_t cap = pp.mask + 1;
      while (want * 4 > cap * 3) cap *= 2;
      pp.rehash(cap);
    }
  }
  // Block pass: compose keys into a scratch buffer, hash + prefetch the
  // slots, then probe — overlaps the table's DRAM misses across the block.
  constexpr int64_t B = 32;
  std::vector<char> scratch;
  scratch.reserve(B * 96);
  int64_t rows[B];
  uint32_t offs[B + 1];
  uint64_t hs[B];
  for (int64_t base = 0; base < n; base += B) {
    int64_t lim = base + B < n ? base + B : n;
    int64_t m = 0;
    scratch.clear();
    offs[0] = 0;
    for (int64_t i = base; i < lim; ++i) {
      int64_t a = action[i];
      bool is_add = a == add_val, is_del = a == del_val;
      if (mode == 0 && !is_add && !is_del) {
        keep[i] = 0;
        existed[i] = 0;
        continue;
      }
      if (mode == 1 && !is_add) continue;  // cancels never mark
      if (mode == 2 && !existed[i]) continue;
      uint32_t si = sym_idx[i], ui = uuid_idx[i];
      scratch.insert(scratch.end(), syms.data + syms.offs[si],
                     syms.data + syms.offs[si + 1]);
      scratch.push_back(kSep);
      scratch.insert(scratch.end(), uuids.data + uuids.offs[ui],
                     uuids.data + uuids.offs[ui + 1]);
      scratch.push_back(kSep);
      auto [op, olen] = trim_padded(oids + i * oid_width, oid_width);
      scratch.insert(scratch.end(), op, op + olen);
      rows[m] = i;
      offs[m + 1] = static_cast<uint32_t>(scratch.size());
      ++m;
    }
    for (int64_t j = 0; j < m; ++j) {
      hs[j] = hash_bytes(scratch.data() + offs[j], offs[j + 1] - offs[j]);
      __builtin_prefetch(&pp.slots[hs[j] & pp.mask]);
    }
    // Staged speculative prefetch along the expected hit path: the slot
    // line is in flight from the loop above; touch it to prefetch the
    // StrRef entry it references, then the key bytes that entry points
    // at. Each stage runs across the whole block, so the three dependent
    // misses of a probe overlap block-wide instead of serializing
    // per key. Pure hints — stage 3's erase/insert re-probes for real
    // (tombstoning or a rehash mid-block only wastes a prefetch).
    const StrRef* krefs[B];
    for (int64_t j = 0; j < m; ++j) {
      const PrePool::Slot& s = pp.slots[hs[j] & pp.mask];
      if (s.h == hs[j] && s.ref > 0) {
        krefs[j] = &pp.keys[static_cast<size_t>(s.ref - 1)];
        __builtin_prefetch(krefs[j]);
      } else {
        krefs[j] = nullptr;
      }
    }
    for (int64_t j = 0; j < m; ++j) {
      if (krefs[j]) __builtin_prefetch(krefs[j]->p);
    }
    for (int64_t j = 0; j < m; ++j) {
      const char* kp = scratch.data() + offs[j];
      size_t kn = offs[j + 1] - offs[j];
      int64_t i = rows[j];
      if (mode != 0) {
        pp.insert_hashed(kp, kn, hs[j]);
      } else {
        bool ex = pp.erase_hashed(kp, kn, hs[j]);
        existed[i] = ex ? 1 : 0;
        keep[i] = (action[i] == del_val) ? 1 : (ex ? 1 : 0);
      }
    }
  }
  return 0;
}

// -------------------------------------------------------------- utilities

// Decode one grid's device-compacted events into final event columns in
// the reference's global emission order (arrival index, then record order
// within the op) — the C++ form of frames._decode_compact + its sort.
// All inputs are int64 host arrays (the Python side slices the fetched
// device buffers to [nf]/[nc] and widens); outputs are preallocated
// [nf+nc] columns. Stable two-pass counting sort over arrival (bounded by
// the frame's order count) replaces the numpy argsort.
int64_t go_decode_compact(
    int64_t n_rows, int64_t t_len, int64_t k, int64_t nf, int64_t nc,
    int64_t frame_n,
    // fills [nf]
    const int64_t* f_src, const int64_t* f_price, const int64_t* f_qty,
    const int64_t* f_moid, const int64_t* f_muid, const int64_t* f_mvol,
    const int64_t* f_after,
    // cancels [nc]
    const int64_t* c_src, const int64_t* c_vol,
    // packed-op meta [m]
    int64_t m, const int64_t* op_row, const int64_t* op_t,
    const int64_t* op_arrival, const int64_t* op_lane,
    const int64_t* op_uid, const int64_t* op_oid, const int64_t* op_side,
    const int64_t* op_price, const int64_t* op_base,
    const int64_t* op_is_market,
    // outputs [nf+nc]
    int64_t* arrival, uint8_t* is_cancel, int64_t* symbol_id,
    int64_t* taker_uid, int64_t* taker_oid, int8_t* taker_side,
    int64_t* taker_price, int64_t* taker_volume, int64_t* maker_uid,
    int64_t* maker_oid, int64_t* fill_price, int64_t* maker_volume,
    int64_t* match_volume, uint8_t* is_market) {
  // (row, t) -> packed-op index join table.
  std::vector<int32_t> op_index(
      static_cast<size_t>(n_rows) * static_cast<size_t>(t_len), -1);
  for (int64_t i = 0; i < m; ++i)
    op_index[static_cast<size_t>(op_row[i] * t_len + op_t[i])] =
        static_cast<int32_t>(i);

  // The op meta arrives as 10 parallel column arrays; per-event access by
  // `pos` is random, so gather the 7 fields an event needs into one
  // 64-byte struct first (sequential pass) — each event then touches ONE
  // meta cache line instead of seven.
  struct OpMeta {
    int64_t arrival, lane, uid, oid, side, price, base;
    int64_t mkt;
  };
  std::vector<OpMeta> om(static_cast<size_t>(m));
  for (int64_t i = 0; i < m; ++i)
    om[static_cast<size_t>(i)] = {op_arrival[i], op_lane[i],  op_uid[i],
                                  op_oid[i],     op_side[i],  op_price[i],
                                  op_base[i],    op_is_market[i]};

  int64_t ne = nf + nc;
  std::vector<int64_t> ev_pos(static_cast<size_t>(ne));   // op index
  std::vector<int64_t> ev_arr(static_cast<size_t>(ne));   // arrival
  std::vector<int64_t> counts(static_cast<size_t>(frame_n) + 1, 0);
  constexpr int64_t PF = 12;  // software prefetch distance
  for (int64_t e = 0; e < nf; ++e) {
    int64_t src = f_src[e];
    int64_t pos = op_index[static_cast<size_t>(src / k)];
    if (pos < 0) return -1;  // fill without a packed ADD: corrupt
    ev_pos[static_cast<size_t>(e)] = pos;
  }
  for (int64_t e = 0; e < nc; ++e) {
    int64_t pos = op_index[static_cast<size_t>(c_src[e])];
    if (pos < 0) return -1;
    ev_pos[static_cast<size_t>(nf + e)] = pos;
  }
  for (int64_t e = 0; e < ne; ++e) {
    if (e + PF < ne)
      __builtin_prefetch(&om[static_cast<size_t>(ev_pos[e + PF])]);
    int64_t a = om[static_cast<size_t>(ev_pos[e])].arrival;
    ev_arr[static_cast<size_t>(e)] = a;
    ++counts[static_cast<size_t>(a)];
  }
  int64_t run = 0;
  for (size_t a = 0; a < counts.size(); ++a) {
    int64_t c = counts[a];
    counts[a] = run;
    run += c;
  }
  // Counting-sort permutation, then emit in DESTINATION order: the 14
  // output columns become pure sequential streams (the random side —
  // event + meta structs — is prefetched ahead), instead of 14 random
  // cache-line RFOs per event.
  std::vector<int64_t> src_of(static_cast<size_t>(ne));
  for (int64_t e = 0; e < ne; ++e)
    src_of[static_cast<size_t>(
        counts[static_cast<size_t>(ev_arr[static_cast<size_t>(e)])]++)] = e;
  for (int64_t dst = 0; dst < ne; ++dst) {
    if (dst + PF < ne) {
      int64_t en = src_of[static_cast<size_t>(dst + PF)];
      __builtin_prefetch(&ev_pos[en]);
      if (en < nf) {
        __builtin_prefetch(&f_price[en]);
        __builtin_prefetch(&f_qty[en]);
      }
    }
    if (dst + PF / 2 < ne) {
      int64_t en = src_of[static_cast<size_t>(dst + PF / 2)];
      __builtin_prefetch(&om[static_cast<size_t>(ev_pos[en])]);
    }
    int64_t e = src_of[static_cast<size_t>(dst)];
    bool cancel = e >= nf;
    const OpMeta& o = om[static_cast<size_t>(ev_pos[e])];
    arrival[dst] = o.arrival;
    is_cancel[dst] = cancel ? 1 : 0;
    symbol_id[dst] = o.lane;
    taker_uid[dst] = o.uid;
    taker_oid[dst] = o.oid;
    taker_side[dst] = static_cast<int8_t>(o.side);
    taker_price[dst] = o.price;
    if (cancel) {
      int64_t e2 = e - nf;
      int64_t vol = c_vol[e2];
      taker_volume[dst] = vol;
      maker_uid[dst] = o.uid;
      maker_oid[dst] = o.oid;
      fill_price[dst] = o.price;
      maker_volume[dst] = vol;
      match_volume[dst] = 0;
      is_market[dst] = 0;
    } else {
      taker_volume[dst] = f_after[e];
      maker_uid[dst] = f_muid[e];
      maker_oid[dst] = f_moid[e];
      fill_price[dst] = f_price[e] + o.base;
      maker_volume[dst] = f_mvol[e];
      match_volume[dst] = f_qty[e];
      is_market[dst] = o.mkt ? 1 : 0;
    }
  }
  return 0;
}

// Fused grid pack: one linear pass selects the frame ops landing in this
// grid's time window and emits (a) the DEVICE-UPLOAD columns — a [7, m]
// field matrix plus the [m] flat grid index each op scatters to ON
// DEVICE — and (b) the packed-op meta columns the event decoder needs,
// replacing ~20 separate numpy mask/scatter passes in
// frames.pack_frame_grids. Emitting columns instead of padded [R, T]
// grids keeps the host->device transfer O(ops): a Zipf frame's deep
// tail grids are ~1% occupied, and uploading their padding cost more
// than the matching (the device rebuilds the padded grid with one
// scatter — frames._scatter_grid).
//
// The pass walks `idx` (n_sub candidate op indices into the frame-global
// field arrays): a frame that splits into a train of grids hands each
// grid only the ops still alive at its time offset, so a G-grid train
// costs O(sum of survivors), not O(G * frame). cols is [7, m] in
// _GRID_FIELDS order (action, side, kind, price, volume, oid, uid),
// int32 or int64 (val_itemsize). Meta outputs are int64 [m] where
// m = |{j : t_off <= t[idx[j]] < t_off+t_grid}| (the caller sizes them
// with one count pass); meta arrival carries the ORIGINAL frame index
// idx[j]. Returns the number packed (must equal m) or -1 on a row/t out
// of grid bounds (corrupt input).
int64_t go_pack_grid(
    int64_t n_sub, const int64_t* idx, const int64_t* row_of,
    const int64_t* lanes, const int64_t* t,
    int64_t t_off, int64_t t_grid, int64_t n_rows,
    const int64_t* action, const int64_t* side, const int64_t* kind,
    const int64_t* price, const int64_t* volume, const int64_t* oid_ids,
    const int64_t* uid_ids, const int64_t* bases, int64_t market_val,
    int64_t add_val,
    void* cols, void* flat_idx, int64_t stride, int64_t val_itemsize,
    int64_t* m_lane, int64_t* m_row, int64_t* m_t, int64_t* m_arrival,
    int64_t* m_action, int64_t* m_side, int64_t* m_market, int64_t* m_price,
    int64_t* m_base, int64_t* m_oid, int64_t* m_uid) {
  // `stride` = the cols matrix's padded column count (a pow2 class, so
  // upload shapes stay compile-stable); rows are written at [f*stride+j].
  bool wide = val_itemsize == 8;
  int64_t m = stride;
  int64_t j = 0;
  for (int64_t s = 0; s < n_sub; ++s) {
    int64_t i = idx[s];
    int64_t ti = t[i];
    if (ti < t_off || ti >= t_off + t_grid) continue;
    int64_t tt = ti - t_off;
    int64_t r = row_of[lanes[i]];  // lane -> grid row (identity when full)
    if (r < 0 || r >= n_rows) return -1;
    int64_t flat = r * t_grid + tt;
    int64_t a = action[i];
    // The op's kind word is the wire's number on an ADD; a cancel ignores
    // its kind (0 on the device).
    int64_t k = a == add_val ? kind[i] : 0;
    bool is_mkt = k == market_val && a == add_val;
    int64_t p_dev = is_mkt ? 0 : price[i] - bases[i];
    if (!wide) {
      // An add that cannot rest (IOC, FOK) is no part of the lane's price
      // envelope, so its limit may lie outside the rebased 32-bit window:
      // clamp it one past the farthest representable resting price
      // (step.TAKER_PRICE_MAX32), which crosses exactly what the true
      // limit crosses. A price inside the envelope is untouched.
      constexpr int64_t kTakerMax32 = 2147483647;
      if (p_dev > kTakerMax32) p_dev = kTakerMax32;
      if (p_dev < -kTakerMax32) p_dev = -kTakerMax32;
    }
    int64_t vals[7] = {a,         side[i],     k,          p_dev,
                       volume[i], oid_ids[i],  uid_ids[i]};
    if (wide) {
      auto* c = static_cast<int64_t*>(cols);
      for (int f = 0; f < 7; ++f) c[f * m + j] = vals[f];
    } else {
      auto* c = static_cast<int32_t*>(cols);
      for (int f = 0; f < 7; ++f)
        c[f * m + j] = static_cast<int32_t>(vals[f]);
    }
    static_cast<int32_t*>(flat_idx)[j] = static_cast<int32_t>(flat);
    m_lane[j] = lanes[i];
    m_row[j] = r;
    m_t[j] = tt;
    m_arrival[j] = i;
    m_action[j] = a;
    m_side[j] = side[i];
    m_market[j] = is_mkt ? 1 : 0;
    m_price[j] = price[i];
    m_base[j] = bases[i];
    m_oid[j] = oid_ids[i];
    m_uid[j] = uid_ids[i];
    ++j;
  }
  return j;
}

// Per-lane occurrence index in arrival order: out_t[i] = number of earlier
// kept rows with the same lane (-1 for dropped rows). Replaces the numpy
// stable-argsort/segment trick in frames._frame_arrays (O(n log n) and
// ~0.1 us/order at frame shape) with one linear pass.
void go_occurrences(const int64_t* lanes, const uint8_t* keep, int64_t n,
                    int64_t n_lanes, int64_t* out_t) {
  std::vector<int64_t> cnt(static_cast<size_t>(n_lanes), 0);
  for (int64_t i = 0; i < n; ++i) {
    if (keep && !keep[i]) {
      out_t[i] = -1;
      continue;
    }
    out_t[i] = cnt[static_cast<size_t>(lanes[i])]++;
  }
}

}  // extern "C"

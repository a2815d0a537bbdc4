// Native WAL key-value engine behind the Store actor.
//
// TPU-native counterpart of the reference's RocksDB storage layer
// (reference store/src/lib.rs:15-92, store/Cargo.toml:9).  RocksDB is a
// poor fit here: the consensus store holds kilobyte-scale protocol
// objects with a working set that always fits in memory, and the only
// durability requirement is crash-recovery replay (SURVEY.md §5 "the
// store IS the checkpoint").  So the engine is an append-only WAL with
// an in-memory open-addressing index — O(1) gets with zero read
// amplification, one sequential write per put or per write batch.
//
// WAL record format (little-endian), shared bit-for-bit with the Python
// WalEngine (hotstuff_tpu/store/engine.py) so either implementation can
// recover the other's files:
//   u32 klen | u32 vlen | key bytes | value bytes
//   vlen == 0xFFFFFFFF marks a tombstone (delete; no value bytes).
//
// A write batch (hs_put_many) takes its records already packed in that
// format and appends the buffer as it is with one write: the log holds
// byte for byte what hs_put a record would have left.  A torn tail
// inside a batch replays to the whole records before the tear.
//
// Durability modes (hs_open's fsync_mode), an append being one hs_put,
// hs_delete or hs_put_many:
//   0 = flush to the OS page cache per append (survives process death)
//   1 = fdatasync per append              (survives OS/power loss)
//   2 = fdatasync on close only
//
// Compaction: on open, after replay, if the log carries more than
// COMPACT_RATIO x live bytes (and is at least COMPACT_MIN bytes), live
// records are rewritten to a fresh log which atomically replaces the old
// one — bounding disk growth across restarts without a background
// thread racing the single writer.
//
// C ABI (consumed via ctypes from hotstuff_tpu/store/native.py):
//   hs_open / hs_put / hs_put_many / hs_get / hs_get_many / hs_delete /
//   hs_keys_blob / hs_count / hs_compact / hs_wal_bytes / hs_free /
//   hs_close

#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>

namespace {

constexpr uint32_t kTombstone = 0xFFFFFFFFu;
constexpr double kCompactRatio = 2.0;
constexpr uint64_t kCompactMin = 1 << 20;  // 1 MiB

struct Engine {
  std::string dir;
  std::string wal_path;
  int fd = -1;
  int fsync_mode = 0;
  uint64_t wal_bytes = 0;   // current log size
  uint64_t live_bytes = 0;  // bytes a compacted log would occupy
  std::unordered_map<std::string, std::string> index;
};

uint64_t record_size(size_t klen, size_t vlen) {
  return 8 + klen + vlen;
}

bool write_all(int fd, const uint8_t* p, size_t n) {
  while (n > 0) {
    ssize_t w = ::write(fd, p, n);
    if (w < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    p += w;
    n -= static_cast<size_t>(w);
  }
  return true;
}

// One append: the bytes in one write, then the sync mode 1 asks for.
bool append_bytes(Engine* e, const uint8_t* p, size_t n) {
  if (!write_all(e->fd, p, n)) return false;
  e->wal_bytes += n;
  if (e->fsync_mode == 1) {
    if (::fdatasync(e->fd) != 0) return false;
  }
  return true;
}

bool append_record(Engine* e, const uint8_t* k, uint32_t klen,
                   const uint8_t* v, uint32_t vlen, bool tombstone) {
  uint8_t hdr[8];
  uint32_t vfield = tombstone ? kTombstone : vlen;
  std::memcpy(hdr, &klen, 4);
  std::memcpy(hdr + 4, &vfield, 4);
  std::vector<uint8_t> buf;
  buf.reserve(8 + klen + (tombstone ? 0 : vlen));
  buf.insert(buf.end(), hdr, hdr + 8);
  buf.insert(buf.end(), k, k + klen);
  if (!tombstone && vlen > 0) buf.insert(buf.end(), v, v + vlen);
  return append_bytes(e, buf.data(), buf.size());
}

void index_put(Engine* e, const uint8_t* k, uint32_t klen, const uint8_t* v,
               uint32_t vlen) {
  std::string key(reinterpret_cast<const char*>(k), klen);
  auto it = e->index.find(key);
  if (it != e->index.end()) {
    e->live_bytes -= record_size(it->first.size(), it->second.size());
  }
  e->live_bytes += record_size(klen, vlen);
  e->index[std::move(key)].assign(reinterpret_cast<const char*>(v), vlen);
}

// Replay the WAL into the index; truncate any torn tail.  Returns false
// only on I/O errors (a missing file is fine).
bool replay(Engine* e) {
  FILE* f = std::fopen(e->wal_path.c_str(), "rb");
  if (f == nullptr) return errno == ENOENT;
  std::fseek(f, 0, SEEK_END);
  long size = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  std::vector<uint8_t> data(static_cast<size_t>(size));
  if (size > 0 && std::fread(data.data(), 1, data.size(), f) != data.size()) {
    std::fclose(f);
    return false;
  }
  std::fclose(f);

  size_t off = 0, n = data.size(), valid_end = 0;
  while (off + 8 <= n) {
    uint32_t klen, vfield;
    std::memcpy(&klen, data.data() + off, 4);
    std::memcpy(&vfield, data.data() + off + 4, 4);
    off += 8;
    if (vfield == kTombstone) {
      if (off + klen > n) break;  // torn tail
      std::string key(reinterpret_cast<char*>(data.data() + off), klen);
      off += klen;
      auto it = e->index.find(key);
      if (it != e->index.end()) {
        e->live_bytes -= record_size(it->first.size(), it->second.size());
        e->index.erase(it);
      }
    } else {
      if (off + klen + static_cast<uint64_t>(vfield) > n) break;  // torn tail
      std::string key(reinterpret_cast<char*>(data.data() + off), klen);
      off += klen;
      std::string val(reinterpret_cast<char*>(data.data() + off), vfield);
      off += vfield;
      auto it = e->index.find(key);
      if (it != e->index.end()) {
        e->live_bytes -= record_size(it->first.size(), it->second.size());
      }
      e->live_bytes += record_size(key.size(), val.size());
      e->index[std::move(key)] = std::move(val);
    }
    valid_end = off;
  }
  e->wal_bytes = valid_end;
  if (valid_end < n) {
    if (::truncate(e->wal_path.c_str(), static_cast<off_t>(valid_end)) != 0) {
      return false;
    }
  }
  return true;
}

// Rewrite live records to a fresh log and atomically swap it in.
bool compact(Engine* e) {
  std::string tmp = e->wal_path + ".compact";
  int tfd = ::open(tmp.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
  if (tfd < 0) return false;
  uint64_t written = 0;
  for (const auto& [key, val] : e->index) {
    uint8_t hdr[8];
    uint32_t klen = static_cast<uint32_t>(key.size());
    uint32_t vlen = static_cast<uint32_t>(val.size());
    std::memcpy(hdr, &klen, 4);
    std::memcpy(hdr + 4, &vlen, 4);
    if (!write_all(tfd, hdr, 8) ||
        !write_all(tfd, reinterpret_cast<const uint8_t*>(key.data()), klen) ||
        !write_all(tfd, reinterpret_cast<const uint8_t*>(val.data()), vlen)) {
      ::close(tfd);
      ::unlink(tmp.c_str());
      return false;
    }
    written += record_size(klen, vlen);
  }
  if (::fdatasync(tfd) != 0 || ::close(tfd) != 0) {
    ::unlink(tmp.c_str());
    return false;
  }
  if (e->fd >= 0) ::close(e->fd);
  if (::rename(tmp.c_str(), e->wal_path.c_str()) != 0) {
    e->fd = ::open(e->wal_path.c_str(), O_WRONLY | O_APPEND | O_CREAT, 0644);
    return false;
  }
  e->fd = ::open(e->wal_path.c_str(), O_WRONLY | O_APPEND, 0644);
  e->wal_bytes = written;
  e->live_bytes = written;
  return e->fd >= 0;
}

}  // namespace

extern "C" {

void* hs_open(const char* path, int fsync_mode) {
  auto* e = new Engine();
  e->dir = path;
  e->fsync_mode = fsync_mode;
  ::mkdir(path, 0755);  // EEXIST is fine
  e->wal_path = e->dir + "/wal.log";
  if (!replay(e)) {
    delete e;
    return nullptr;
  }
  if (e->wal_bytes >= kCompactMin &&
      static_cast<double>(e->wal_bytes) >
          kCompactRatio * static_cast<double>(e->live_bytes)) {
    if (!compact(e)) {
      delete e;
      return nullptr;
    }
  } else {
    e->fd = ::open(e->wal_path.c_str(), O_WRONLY | O_APPEND | O_CREAT, 0644);
    if (e->fd < 0) {
      delete e;
      return nullptr;
    }
  }
  return e;
}

int hs_put(void* h, const uint8_t* k, uint32_t klen, const uint8_t* v,
           uint32_t vlen) {
  auto* e = static_cast<Engine*>(h);
  if (vlen == kTombstone) return -1;  // reserved
  if (!append_record(e, k, klen, v, vlen, false)) return -1;
  index_put(e, k, klen, v, vlen);
  return 0;
}

// A write batch: `buf` holds `n` bytes of whole put records in the WAL's
// own format.  Checked first (a record that overruns the buffer or is a
// tombstone refuses the batch, nothing written), appended with one
// write, then indexed in order, so a key given twice keeps its last
// value.
int hs_put_many(void* h, const uint8_t* buf, uint64_t n) {
  auto* e = static_cast<Engine*>(h);
  uint64_t off = 0;
  while (off < n) {
    if (n - off < 8) return -1;
    uint32_t klen, vlen;
    std::memcpy(&klen, buf + off, 4);
    std::memcpy(&vlen, buf + off + 4, 4);
    if (vlen == kTombstone) return -1;
    uint64_t body = static_cast<uint64_t>(klen) + vlen;
    if (n - off - 8 < body) return -1;
    off += 8 + body;
  }
  if (n == 0) return 0;
  if (!append_bytes(e, buf, n)) return -1;
  for (off = 0; off < n;) {
    uint32_t klen, vlen;
    std::memcpy(&klen, buf + off, 4);
    std::memcpy(&vlen, buf + off + 4, 4);
    index_put(e, buf + off + 8, klen, buf + off + 8 + klen, vlen);
    off += 8 + static_cast<uint64_t>(klen) + vlen;
  }
  return 0;
}

int hs_get(void* h, const uint8_t* k, uint32_t klen, uint8_t** out,
           uint32_t* outlen) {
  auto* e = static_cast<Engine*>(h);
  auto it = e->index.find(std::string(reinterpret_cast<const char*>(k), klen));
  if (it == e->index.end()) return -1;
  *outlen = static_cast<uint32_t>(it->second.size());
  *out = static_cast<uint8_t*>(std::malloc(it->second.size() ? it->second.size() : 1));
  if (*out == nullptr) return -2;
  std::memcpy(*out, it->second.data(), it->second.size());
  return 0;
}

// Several gets in one crossing.  The `count` keys lie end to end in
// `keys`, their lengths in `klens`; the answer is one blob of `count`
// u32 value lengths (0xFFFFFFFF for a key not there), then the values
// that are there, end to end, in the keys' order.
int hs_get_many(void* h, const uint32_t* klens, uint32_t count,
                const uint8_t* keys, uint8_t** out, uint64_t* outlen) {
  auto* e = static_cast<Engine*>(h);
  std::vector<const std::string*> found(count, nullptr);
  uint64_t total = 4ull * count;
  for (uint32_t i = 0; i < count; i++) {
    auto it = e->index.find(
        std::string(reinterpret_cast<const char*>(keys), klens[i]));
    keys += klens[i];
    if (it != e->index.end()) {
      found[i] = &it->second;
      total += it->second.size();
    }
  }
  auto* buf = static_cast<uint8_t*>(std::malloc(total ? total : 1));
  if (buf == nullptr) return -2;
  uint8_t* at = buf + 4ull * count;
  for (uint32_t i = 0; i < count; i++) {
    uint32_t vlen =
        found[i] ? static_cast<uint32_t>(found[i]->size()) : kTombstone;
    std::memcpy(buf + 4ull * i, &vlen, 4);
    if (found[i]) {
      std::memcpy(at, found[i]->data(), found[i]->size());
      at += found[i]->size();
    }
  }
  *out = buf;
  *outlen = total;
  return 0;
}

int hs_delete(void* h, const uint8_t* k, uint32_t klen) {
  auto* e = static_cast<Engine*>(h);
  if (!append_record(e, k, klen, nullptr, 0, true)) return -1;
  std::string key(reinterpret_cast<const char*>(k), klen);
  auto it = e->index.find(key);
  if (it != e->index.end()) {
    e->live_bytes -= record_size(it->first.size(), it->second.size());
    e->index.erase(it);
  }
  return 0;
}

// All keys as one blob: u32 count | (u32 klen | key bytes)*
int hs_keys_blob(void* h, uint8_t** out, uint64_t* outlen) {
  auto* e = static_cast<Engine*>(h);
  uint64_t total = 4;
  for (const auto& [key, _] : e->index) total += 4 + key.size();
  auto* buf = static_cast<uint8_t*>(std::malloc(total));
  if (buf == nullptr) return -2;
  uint32_t count = static_cast<uint32_t>(e->index.size());
  std::memcpy(buf, &count, 4);
  uint64_t off = 4;
  for (const auto& [key, _] : e->index) {
    uint32_t klen = static_cast<uint32_t>(key.size());
    std::memcpy(buf + off, &klen, 4);
    off += 4;
    std::memcpy(buf + off, key.data(), key.size());
    off += key.size();
  }
  *out = buf;
  *outlen = total;
  return 0;
}

uint64_t hs_count(void* h) {
  return static_cast<Engine*>(h)->index.size();
}

uint64_t hs_wal_bytes(void* h) {
  return static_cast<Engine*>(h)->wal_bytes;
}

int hs_compact(void* h) {
  return compact(static_cast<Engine*>(h)) ? 0 : -1;
}

void hs_free(uint8_t* p) { std::free(p); }

void hs_close(void* h) {
  auto* e = static_cast<Engine*>(h);
  if (e->fd >= 0) {
    if (e->fsync_mode != 0) ::fdatasync(e->fd);
    ::close(e->fd);
  }
  delete e;
}

}  // extern "C"

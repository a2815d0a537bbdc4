// Sanitizer stress harness for the native layer (ISSUE 12).
//
// Compiled TOGETHER with transport.cpp and store_engine.cpp into a
// standalone executable (build/san_stress_{tsan,asan}) — a sanitized
// .so dlopened into an uninstrumented Python would miss the runtime
// interceptors, so the stress drives the C ABI directly:
//
//   store:     per-thread WAL engines (put/get/delete/compact/replay
//              round-trips) plus one SHARED engine serialized by an
//              external mutex — the engine is single-writer by design
//              (hotstuff_tpu/store owns one per node), so the shared
//              mode models the documented discipline, not free-for-all
//              concurrency.
//   transport: one reactor, multi-threaded ht_send/ht_reply against the
//              reactor thread's epoll loop and the ht_next drain —
//              every mutex-protected queue handoff in transport.cpp
//              under genuine cross-thread fire.
//   wavepack:  one wave packer ring (wave_pack.cpp), four packer
//              threads racing wp_pack_vote against a sealer thread
//              doing wp_seal/wp_arena_info/column reads/wp_recycle and
//              periodic wp_discard — the production topology (reactor
//              thread packs, verifier slot threads seal and recycle)
//              with the thread count turned up.
//
// Exit 0 and "SAN_STRESS OK" on success; any sanitizer report fails
// the process via halt_on_error=1 (set by scripts/san_check.py).

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include <unistd.h>

extern "C" {
// store_engine.cpp
void* hs_open(const char* path, int fsync_mode);
int hs_put(void* h, const uint8_t* k, uint32_t klen, const uint8_t* v,
           uint32_t vlen);
int hs_put_many(void* h, const uint8_t* buf, uint64_t n);
int hs_get_many(void* h, const uint32_t* klens, uint32_t count,
                const uint8_t* keys, uint8_t** out, uint64_t* outlen);
int hs_get(void* h, const uint8_t* k, uint32_t klen, uint8_t** out,
           uint32_t* outlen);
int hs_delete(void* h, const uint8_t* k, uint32_t klen);
uint64_t hs_count(void* h);
int hs_compact(void* h);
void hs_free(uint8_t* p);
void hs_close(void* h);
// transport.cpp
void* ht_start();
long ht_listen(void* rp, const char* ip, int port);
long ht_connect(void* rp, const char* ip, int port);
int ht_send(void* rp, long peer, const uint8_t* data, int len);
int ht_reply(void* rp, long conn, const uint8_t* data, int len);
int ht_next(void* rp, long* src, int* kind, uint8_t* buf, int cap);
int ht_set_read_paused(void* rp, long conn, int paused);
int ht_close_conn(void* rp, long conn);
void ht_stop(void* rp);
// wave_pack.cpp
void* wp_create(int capacity, int ring_depth);
void wp_destroy(void* h);
int wp_set_pad(void* h, const uint8_t* dig, const uint8_t* pk,
               const uint8_t* sig);
int wp_probe_vote(const uint8_t* frame, long n);
long wp_pack_vote(void* h, const uint8_t* frame, long n, uint8_t* digest_out);
long wp_count(void* h);
long wp_seal(void* h, long n_take);
int wp_arena_info(void* h, long arena, uint64_t out[5]);
int wp_recycle(void* h, long arena);
int wp_discard(void* h);
int wp_counters(void* h, uint64_t* out, int cap);
long wp_parse_producer(const uint8_t* frame, long n, uint8_t* digests_out,
                       uint64_t* spans_out);
}

namespace {

constexpr int kStoreThreads = 4;
constexpr int kStoreOps = 400;
constexpr int kSendThreads = 4;
constexpr int kSendsPerThread = 250;

bool g_failed = false;

void fail(const char* what) {
  std::fprintf(stderr, "SAN_STRESS FAIL: %s\n", what);
  g_failed = true;
}

// ---- store stress ----------------------------------------------------------

std::string key_of(int t, int i) {
  char buf[32];
  std::snprintf(buf, sizeof buf, "k/%d/%d", t, i % 37);
  return buf;
}

void pack_u32(std::string* to, uint32_t v) {
  to->append(reinterpret_cast<const char*>(&v), 4);
}

// A write batch of `n` records beside the per-record puts, keys of the
// same space (the last of them twice), read back with one hs_get_many.
bool batch_round_trip(void* h, int t, int i, int n) {
  std::string batch, keys, last;
  std::vector<uint32_t> klens;
  for (int j = 0; j <= n; j++) {
    std::string k = key_of(t, i + (j < n ? j : n - 1));
    last.assign(1 + (i + j) % 80, char('a' + t));
    pack_u32(&batch, k.size());
    pack_u32(&batch, last.size());
    batch += k + last;
    if (j < n) {
      klens.push_back(k.size());
      keys += k;
    }
  }
  if (hs_put_many(h, (const uint8_t*)batch.data(), batch.size()) != 0)
    return false;
  // a batch cut short is refused whole, nothing written
  if (hs_put_many(h, (const uint8_t*)batch.data(), batch.size() - 1) == 0)
    return false;
  uint8_t* out = nullptr;
  uint64_t outlen = 0;
  if (hs_get_many(h, klens.data(), n, (const uint8_t*)keys.data(), &out,
                  &outlen) != 0)
    return false;
  // every key is there, and the key given twice holds its last value
  uint64_t off = 4ull * n;
  bool ok = outlen >= off;
  for (int j = 0; j < n && ok; j++) {
    uint32_t vlen;
    std::memcpy(&vlen, out + 4 * j, 4);
    ok = vlen != 0xFFFFFFFFu && off + vlen <= outlen;
    if (ok && j == n - 1)
      ok = vlen == last.size() && std::memcmp(out + off, last.data(), vlen) == 0;
    off += vlen;
  }
  hs_free(out);
  return ok && off == outlen;
}

void store_worker(const std::string& dir, int t) {
  std::string path = dir + "/own_" + std::to_string(t) + ".wal";
  void* h = hs_open(path.c_str(), 0);
  if (!h) return fail("hs_open(per-thread)");
  for (int i = 0; i < kStoreOps; i++) {
    std::string k = key_of(t, i);
    std::string v(1 + (i * 7) % 96, char('a' + t));
    if (hs_put(h, (const uint8_t*)k.data(), k.size(),
               (const uint8_t*)v.data(), v.size()) != 0)
      return fail("hs_put");
    uint8_t* out = nullptr;
    uint32_t outlen = 0;
    if (hs_get(h, (const uint8_t*)k.data(), k.size(), &out, &outlen) != 0 ||
        outlen != v.size() || std::memcmp(out, v.data(), outlen) != 0) {
      hs_free(out);
      return fail("hs_get round-trip");
    }
    hs_free(out);
    if (i % 11 == 3)
      hs_delete(h, (const uint8_t*)k.data(), k.size());
    if (i % 5 == 2 && !batch_round_trip(h, t, i, 1 + i % 23))
      return fail("hs_put_many round-trip");
    if (i % 97 == 50) hs_compact(h);
    if (i % 151 == 100) {
      // close/reopen exercises WAL replay + compaction-on-open
      hs_close(h);
      h = hs_open(path.c_str(), 0);
      if (!h) return fail("hs_open(reopen)");
    }
  }
  hs_close(h);
}

void store_stress(const std::string& dir) {
  // per-thread engines: the production topology (one engine per node)
  std::vector<std::thread> ts;
  for (int t = 0; t < kStoreThreads; t++)
    ts.emplace_back(store_worker, dir, t);
  for (auto& th : ts) th.join();

  // one shared engine behind an external mutex: the documented
  // discipline when an engine must cross threads
  std::string path = dir + "/shared.wal";
  void* h = hs_open(path.c_str(), 0);
  if (!h) return fail("hs_open(shared)");
  std::mutex mu;
  std::vector<std::thread> ss;
  for (int t = 0; t < kStoreThreads; t++) {
    ss.emplace_back([&, t] {
      for (int i = 0; i < kStoreOps; i++) {
        std::string k = key_of(t, i);
        std::string v(1 + i % 64, char('A' + t));
        std::lock_guard<std::mutex> g(mu);
        if (hs_put(h, (const uint8_t*)k.data(), k.size(),
                   (const uint8_t*)v.data(), v.size()) != 0)
          return fail("hs_put(shared)");
        if (i % 13 == 7)
          hs_delete(h, (const uint8_t*)k.data(), k.size());
        if (i % 9 == 4 && !batch_round_trip(h, t, i, 1 + i % 23))
          return fail("hs_put_many(shared)");
      }
    });
  }
  for (auto& th : ss) th.join();
  {
    std::lock_guard<std::mutex> g(mu);
    hs_compact(h);
    if (hs_count(h) == 0) fail("shared engine lost every key");
    hs_close(h);
  }
  std::printf("store stress done\n");
}

// ---- transport stress ------------------------------------------------------

void transport_stress() {
  void* rp = ht_start();
  if (!rp) return fail("ht_start");
  long listener = -1;
  int port = 0;
  for (int attempt = 0; attempt < 100 && listener < 0; attempt++) {
    port = 36000 + (int)((getpid() + attempt * 7) % 20000);
    listener = ht_listen(rp, "127.0.0.1", port);
  }
  if (listener < 0) {
    ht_stop(rp);
    return fail("ht_listen");
  }

  std::vector<long> peers;
  for (int i = 0; i < kSendThreads; i++) {
    long p = ht_connect(rp, "127.0.0.1", port);
    if (p < 0) {
      ht_stop(rp);
      return fail("ht_connect");
    }
    peers.push_back(p);
  }

  std::atomic<long> sent{0}, replied{0};
  std::atomic<long> got_accepted{0}, got_peer{0};
  std::atomic<bool> done_sending{false};

  // drain thread: the single ht_next consumer; replies to every 3rd
  // accepted frame so the reply path runs concurrently with senders
  std::thread drain([&] {
    std::vector<uint8_t> buf(1 << 16);
    auto deadline =
        std::chrono::steady_clock::now() + std::chrono::seconds(30);
    long pauses = 0;
    while (std::chrono::steady_clock::now() < deadline) {
      long src = 0;
      int kind = 0;
      int n = ht_next(rp, &src, &kind, buf.data(), (int)buf.size());
      if (n == -1) {
        if (done_sending.load() &&
            got_accepted.load() >= sent.load() &&
            got_peer.load() >= replied.load())
          break;
        usleep(200);
        continue;
      }
      if (n < 0) {
        fail("ht_next buffer too small");
        break;
      }
      if (kind == 1) {  // frame from an accepted conn
        long c = got_accepted.fetch_add(1) + 1;
        if (c % 3 == 0) {
          if (ht_reply(rp, src, buf.data(), n > 64 ? 64 : n) == 0)
            replied.fetch_add(1);
        }
        if (c % 101 == 50 && pauses < 8) {
          // flow-control churn against the reactor thread
          ht_set_read_paused(rp, src, 1);
          ht_set_read_paused(rp, src, 0);
          pauses++;
        }
      } else if (kind == 2) {  // frame from a connected peer (reply)
        got_peer.fetch_add(1);
      }
      // kinds 3/4 (closes) just drain
    }
  });

  std::vector<std::thread> senders;
  for (int t = 0; t < kSendThreads; t++) {
    senders.emplace_back([&, t] {
      std::vector<uint8_t> payload(16 + 97 * t, (uint8_t)t);
      for (int i = 0; i < kSendsPerThread; i++) {
        int len = 1 + (int)((i * 131 + t) % payload.size());
        if (ht_send(rp, peers[t], payload.data(), len) == 0)
          sent.fetch_add(1);
        else
          usleep(100);  // connect still in flight: retry cadence
        if (i % 50 == 49) usleep(500);  // let the reactor breathe
      }
    });
  }
  for (auto& th : senders) th.join();
  done_sending.store(true);
  drain.join();

  if (got_accepted.load() < sent.load())
    fail("transport dropped accepted-side frames");
  if (got_peer.load() < replied.load())
    fail("transport dropped reply frames");

  for (long p : peers) ht_close_conn(rp, p);
  ht_stop(rp);
  std::printf("transport stress done: sent=%ld delivered=%ld replies=%ld\n",
              sent.load(), got_accepted.load(), got_peer.load());
}

// ---- wave-pack stress ------------------------------------------------------

constexpr int kPackThreads = 4;
constexpr int kPacksPerThread = 2000;
constexpr int kArenaCap = 64;
constexpr int kRingDepth = 4;

// Valid 145-byte ed25519 vote frame with deterministic junk contents —
// the packer checks wire shape, not signatures.
void make_vote_frame(uint8_t out[145], int t, int i) {
  std::memset(out, 0, 145);
  out[0] = 1;  // TAG_VOTE
  for (int k = 0; k < 32; k++) out[1 + k] = (uint8_t)(t * 37 + i + k);
  uint64_t rnd = (uint64_t)t << 32 | (uint32_t)i;
  std::memcpy(out + 33, &rnd, 8);  // round (LE on every target we build)
  out[41] = 32;                    // pk_len LE
  for (int k = 0; k < 32; k++) out[45 + k] = (uint8_t)(t + k);
  out[77] = 64;  // sig_len LE
  for (int k = 0; k < 64; k++) out[81 + k] = (uint8_t)(i + k);
}

void wavepack_stress() {
  void* wp = wp_create(kArenaCap, kRingDepth);
  if (!wp) return fail("wp_create");
  uint8_t pad_dig[32], pad_pk[32], pad_sig[64];
  std::memset(pad_dig, 0xA5, sizeof pad_dig);
  std::memset(pad_pk, 0x5A, sizeof pad_pk);
  std::memset(pad_sig, 0x3C, sizeof pad_sig);
  if (wp_set_pad(wp, pad_dig, pad_pk, pad_sig) != 0) {
    wp_destroy(wp);
    return fail("wp_set_pad");
  }

  std::atomic<long> packed{0}, dropped{0};
  std::atomic<bool> done_packing{false};

  // sealer: the verifier-slot role — seal whatever is packed, adopt the
  // column views (read every exposed byte: ASan bounds + TSan ordering
  // vs. the packers), recycle; periodic discard models an ingest resync
  std::thread sealer([&] {
    std::vector<uint8_t> sink(1, 0);
    uint64_t info[5];
    long seals = 0;
    while (true) {
      long c = wp_count(wp);
      if (c <= 0) {
        if (done_packing.load() && wp_count(wp) <= 0) break;
        usleep(100);
        continue;
      }
      long take = c > 16 ? 16 : c;
      long arena = wp_seal(wp, take);
      if (arena == -2) {  // every arena busy: shed like the real plane
        wp_discard(wp);
        continue;
      }
      if (arena < 0) continue;  // packer raced the count snapshot
      if (wp_arena_info(wp, arena, info) != 0) {
        fail("wp_arena_info on sealed arena");
        break;
      }
      if ((long)info[3] != take || (long)info[4] != kArenaCap) {
        fail("wp_arena_info shape mismatch");
        break;
      }
      const uint8_t* dig = (const uint8_t*)(uintptr_t)info[0];
      const uint8_t* pk = (const uint8_t*)(uintptr_t)info[1];
      const uint8_t* sig = (const uint8_t*)(uintptr_t)info[2];
      uint8_t acc = 0;
      for (long r = 0; r < kArenaCap; r++) {  // full fixed shape, pads too
        for (int k = 0; k < 32; k++) acc ^= dig[r * 32 + k];
        for (int k = 0; k < 32; k++) acc ^= pk[r * 32 + k];
        for (int k = 0; k < 64; k++) acc ^= sig[r * 64 + k];
      }
      sink[0] ^= acc;
      if (wp_recycle(wp, arena) != 0) {
        fail("wp_recycle");
        break;
      }
      if (++seals % 97 == 0) wp_discard(wp);
    }
    if (sink[0] == 0xFF) std::printf("(sink)\n");  // keep the reads live
  });

  std::vector<std::thread> packers;
  for (int t = 0; t < kPackThreads; t++) {
    packers.emplace_back([&, t] {
      uint8_t frame[145], digest[32];
      uint64_t spans[8 * 2];
      uint8_t digs[8 * 32];
      for (int i = 0; i < kPacksPerThread; i++) {
        make_vote_frame(frame, t, i);
        if (wp_probe_vote(frame, sizeof frame) != 1) {
          fail("wp_probe_vote rejected a valid frame");
          return;
        }
        long slot = wp_pack_vote(wp, frame, sizeof frame, digest);
        if (slot == -2) {
          dropped.fetch_add(1);  // open arena full: real plane resyncs
          usleep(50);
        } else if (slot >= 0) {
          packed.fetch_add(1);
        } else {
          fail("wp_pack_vote rejected a valid frame");
          return;
        }
        if (i % 53 == 17) {
          // stateless producer parse races the stateful ring paths
          uint8_t pf[6 + 2 * (32 + 4 + 3)];
          pf[0] = 6;  // TAG_PRODUCER_V2
          pf[1] = 2;  // version
          pf[2] = 2; pf[3] = 0; pf[4] = 0; pf[5] = 0;  // count LE
          size_t off = 6;
          for (int item = 0; item < 2; item++) {
            std::memset(pf + off, (uint8_t)(t + item), 32);
            off += 32;
            pf[off] = 3; pf[off + 1] = 0; pf[off + 2] = 0; pf[off + 3] = 0;
            off += 4;
            std::memset(pf + off, 0x42, 3);
            off += 3;
          }
          if (wp_parse_producer(pf, (long)off, digs, spans) != 2) {
            fail("wp_parse_producer rejected a valid frame");
            return;
          }
        }
      }
    });
  }
  for (auto& th : packers) th.join();
  done_packing.store(true);
  sealer.join();

  uint64_t ctr[7] = {0};
  wp_counters(wp, ctr, 7);
  long expect = (long)kPackThreads * kPacksPerThread - dropped.load();
  if ((long)ctr[0] != packed.load() || packed.load() != expect)
    fail("wave-pack lost packed rows");
  if (ctr[3] == 0) fail("wave-pack sealer never sealed");
  if (ctr[3] != ctr[5]) fail("seal/recycle imbalance");
  wp_destroy(wp);
  std::printf("wavepack stress done: packed=%llu seals=%llu moved=%llu "
              "discards=%llu dropped=%ld\n",
              (unsigned long long)ctr[0], (unsigned long long)ctr[3],
              (unsigned long long)ctr[6], (unsigned long long)ctr[4],
              dropped.load());
}

}  // namespace

int main(int argc, char** argv) {
  const char* which = argc > 1 ? argv[1] : "all";
  char tmpl[] = "/tmp/hs_san_XXXXXX";
  char* dir = mkdtemp(tmpl);
  if (!dir) {
    std::fprintf(stderr, "SAN_STRESS FAIL: mkdtemp\n");
    return 1;
  }
  bool all = std::strcmp(which, "all") == 0;
  if (all || std::strcmp(which, "store") == 0) store_stress(dir);
  if (all || std::strcmp(which, "transport") == 0) transport_stress();
  if (all || std::strcmp(which, "wavepack") == 0) wavepack_stress();
  if (g_failed) return 1;
  std::printf("SAN_STRESS OK\n");
  return 0;
}

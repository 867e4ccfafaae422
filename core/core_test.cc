// Native unit tests for the C++ core (run via ctest). The cross-language
// equivalence suite lives in tests/ (pytest drives the C ABI); these cover
// the pieces a pure-C++ build must guarantee on its own: crypto known
// answers, canonical JSON, and a full in-process 4-replica consensus round
// including a view change.
#include <arpa/inet.h>
#include <netinet/in.h>
#include <poll.h>
#include <sched.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <array>
#include <atomic>
#include <chrono>
#include <cerrno>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <map>
#include <memory>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "blake2b.h"
#include "ed25519.h"
#include "flight.h"
#include "json.h"
#include "messages.h"
#include "metrics.h"
#include "net.h"
#include "net_shard.h"
#include "replica.h"
#include "secure.h"
#include "sha512.h"
#include "verifier.h"
#include "verify_pool.h"

namespace {

int g_failures = 0;

#define CHECK(cond)                                                      \
  do {                                                                   \
    if (!(cond)) {                                                       \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
      ++g_failures;                                                      \
    }                                                                    \
  } while (0)

std::string hex(const uint8_t* d, size_t n) { return pbft::to_hex(d, n); }

void test_sha512_vectors() {
  // FIPS 180-2 "abc"
  uint8_t out[64];
  pbft::sha512(out, (const uint8_t*)"abc", 3);
  CHECK(hex(out, 8) == "ddaf35a193617aba");
  pbft::sha512(out, nullptr, 0);
  CHECK(hex(out, 8) == "cf83e1357eefb8bd");
}

void test_blake2b_vector() {
  // blake2b-256("") = 0e5751c0...
  uint8_t out[32];
  pbft::blake2b(out, 32, nullptr, 0);
  CHECK(hex(out, 4) == "0e5751c0");
}

void test_ed25519_rfc8032() {
  // RFC 8032 test 1: empty message.
  uint8_t seed[32], pub[32], sig[64];
  pbft::from_hex(
      "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
      seed, 32);
  pbft::ed25519_public_key(pub, seed);
  CHECK(hex(pub, 32) ==
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a");
  pbft::ed25519_sign(sig, seed, nullptr, 0);
  CHECK(hex(sig, 8) == "e5564300c360ac72");
  CHECK(pbft::ed25519_verify(pub, nullptr, 0, sig));
  sig[0] ^= 1;
  CHECK(!pbft::ed25519_verify(pub, nullptr, 0, sig));
}

// --- ISSUE 39: the scalar arithmetic mod L ----------------------------------
// The plain reference: the bit-serial long division that WAS the library's
// reduction up to PR 38 (one compare-and-subtract of L << shift a bit of the
// quotient), kept here to hold the word-level reduction to, bit for bit.
using u64 = uint64_t;
using u128 = unsigned __int128;

constexpr u64 kRefL[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL, 0ULL,
                          0x1000000000000000ULL};

// x -= L << bitshift when that keeps x >= 0 (x: n 64-bit LE limbs).
bool ref_sub_l_shifted_if_ge(u64* x, int n, int bitshift) {
  u64 tmp[12];
  std::memcpy(tmp, x, n * 8);
  int limb = bitshift / 64, off = bitshift % 64;
  u128 borrow = 0;
  for (int i = 0; i < n; ++i) {
    u128 sub = borrow;
    int j = i - limb;
    u64 part = 0;
    if (j >= 0 && j < 4) part = kRefL[j] << off;
    if (off && j - 1 >= 0 && j - 1 < 4) part |= kRefL[j - 1] >> (64 - off);
    sub += part;
    u128 cur = (u128)tmp[i];
    if (cur >= sub) {
      tmp[i] = (u64)(cur - sub);
      borrow = 0;
    } else {
      tmp[i] = (u64)(cur + (((u128)1) << 64) - sub);
      borrow = 1;
    }
  }
  if (borrow) return false;
  std::memcpy(x, tmp, n * 8);
  return true;
}

// 512-bit value mod L: L's top bit is 2^252, so shifts 259..0 suffice.
void ref_reduce512(u64 out[4], const u64 in[8]) {
  u64 x[12] = {0};
  std::memcpy(x, in, 64);
  for (int shift = 259; shift >= 0; --shift) ref_sub_l_shifted_if_ge(x, 12, shift);
  std::memcpy(out, x, 32);
}

// (a*b + c) mod L, a of na limbs: schoolbook product, then the division.
void ref_muladd(u64 out[4], const u64* a, int na, const u64 b[4],
                const u64 c[4]) {
  u64 wide[9] = {0};
  for (int i = 0; i < na; ++i) {
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a[i] * b[j];
      for (int k = i + j; cur; ++k) {
        cur += wide[k];
        wide[k] = (u64)cur;
        cur >>= 64;
      }
    }
  }
  u128 carry = 0;
  for (int k = 0; k < 9; ++k) {
    carry += (u128)wide[k] + (k < 4 ? c[k] : 0);
    wide[k] = (u64)carry;
    carry >>= 64;
  }
  CHECK(wide[8] == 0);  // a*b + c < 2^512 for any operands
  ref_reduce512(out, wide);
}

struct SplitMix {  // seeded, so a failure repeats
  u64 s;
  u64 next() {
    u64 z = (s += 0x9e3779b97f4a7c15ULL);
    z = (z ^ (z >> 30)) * 0xbf58476d1ce4e5b9ULL;
    z = (z ^ (z >> 27)) * 0x94d049bb133111ebULL;
    return z ^ (z >> 31);
  }
};

// One value through the library's reduction and the reference's.
void check_reduce512(const u64 x[8]) {
  u64 want[4], got[4];
  uint8_t in[64], out[32];
  ref_reduce512(want, x);
  std::memcpy(in, x, 64);
  pbft::ed25519_test_sc_reduce512(out, in);
  std::memcpy(got, out, 32);
  CHECK(std::memcmp(got, want, 32) == 0);
}

// x += 1 (up) or x -= 1 over n limbs, wrapping.
void limbs_step(u64* x, int n, bool up) {
  for (int i = 0; i < n; ++i) {
    if (up ? ++x[i] != 0 : x[i]-- != 0) return;
  }
}

void test_scalar_reduction_vs_long_division() {
  // The edges: around every multiple of L that a limb boundary or a bound
  // in the library's comments names.
  std::vector<std::array<u64, 8>> edges;
  auto around = [&](std::array<u64, 8> x) {  // x - 1, x, x + 1
    limbs_step(x.data(), 8, false);
    for (int k = 0; k < 3; ++k) {
      edges.push_back(x);
      limbs_step(x.data(), 8, true);
    }
  };
  std::array<u64, 8> zero{}, l{}, l2{}, ones;
  ones.fill(~0ULL);
  std::memcpy(l.data(), kRefL, 32);
  for (int i = 0; i < 4; ++i) {
    l2[i] = kRefL[i] << 1 | (i ? kRefL[i - 1] >> 63 : 0);
  }
  around(zero);  // 2^512 - 1, 0, 1
  around(l);
  around(l2);
  for (int bit : {64, 128, 192, 252, 253, 256, 320, 383, 384, 448, 511}) {
    std::array<u64, 8> p{};
    p[bit / 64] = 1ULL << (bit % 64);
    around(p);
  }
  {  // k*L for the largest k that fits 512 bits
    u64 r[4];
    ref_reduce512(r, ones.data());
    std::array<u64, 8> kl = ones;
    u64 borrow = 0;
    for (int i = 0; i < 8; ++i) {
      u128 cur = (u128)kl[i] - (i < 4 ? r[i] : 0) - borrow;
      kl[i] = (u64)cur;
      borrow = (u64)(cur >> 64) & 1;
    }
    around(kl);
  }
  for (int hi = 1; hi <= 8; ++hi) {  // all-ones limbs, low and high runs
    std::array<u64, 8> lo_run{}, hi_run{};
    for (int i = 0; i < hi; ++i) lo_run[i] = hi_run[7 - i] = ~0ULL;
    edges.push_back(lo_run);
    edges.push_back(hi_run);
  }
  for (const auto& e : edges) check_reduce512(e.data());

  SplitMix rng{0x39};
  for (int i = 0; i < 20000; ++i) {
    u64 x[8];
    for (u64& w : x) w = rng.next();
    if (i % 4 == 1) x[7] = x[6] = 0;            // a 384-bit product
    if (i % 4 == 2) x[7] = ~0ULL;               // the largest quotients
    if (i % 4 == 3) x[rng.next() % 8] = 0;      // a zero limb in the chain
    check_reduce512(x);
  }

  // a*b + c in both widths of a, and a + b, on operands at the edges of
  // 256 bits and at random.
  for (int i = 0; i < 6000; ++i) {
    u64 a[4], b[4], c[4], want[4];
    uint8_t ab[32], bb[32], cb[32], out[32];
    for (int k = 0; k < 4; ++k) {
      a[k] = rng.next();
      b[k] = rng.next();
      c[k] = rng.next();
    }
    if (i % 5 == 1) std::memset(a, 0xff, 32);
    if (i % 5 == 2) std::memset(b, 0xff, 32), std::memset(c, 0xff, 32);
    if (i < 5) std::memset(a, 0xff, 32), std::memset(b, 0xff, 32);
    if (i == 0) std::memset(c, 0xff, 32);  // (2^256-1)^2 + 2^256-1: the bound
    std::memcpy(ab, a, 32);
    std::memcpy(bb, b, 32);
    std::memcpy(cb, c, 32);
    ref_muladd(want, a, 4, b, c);
    pbft::ed25519_test_sc_muladd(out, ab, bb, cb);
    CHECK(std::memcmp(out, want, 32) == 0);
    ref_muladd(want, a, 2, b, c);
    pbft::ed25519_test_sc_muladd128(out, ab, bb, cb);
    CHECK(std::memcmp(out, want, 32) == 0);
    // a + b for reduced operands (the library's precondition): the
    // reference is the 512-bit division of the plain sum.
    u64 ar[8] = {0}, br[8] = {0}, sum[8] = {0};
    std::memcpy(ar, a, 32);
    std::memcpy(br, b, 32);
    if (i % 7 == 3) {  // L - 1 + L - 1: the largest sum
      std::memcpy(ar, kRefL, 32);
      std::memcpy(br, kRefL, 32);
      limbs_step(ar, 8, false);
      limbs_step(br, 8, false);
    }
    ref_reduce512(ar, ar);
    ref_reduce512(br, br);
    u128 carry = 0;
    for (int k = 0; k < 5; ++k) {
      carry += (u128)ar[k] + br[k];
      sum[k] = (u64)carry;
      carry >>= 64;
    }
    ref_reduce512(want, sum);
    std::memcpy(ab, ar, 32);
    std::memcpy(bb, br, 32);
    pbft::ed25519_test_sc_add(out, ab, bb);
    CHECK(std::memcmp(out, want, 32) == 0);
  }
}

// What the host crypto costs on the host at hand (PERF.md section 5
// carries the chip host's figures; pbft_signs_total counts the first).
void test_crypto_yardstick() {
  using clock = std::chrono::steady_clock;
  auto us_each = [](clock::time_point t0, int n) {
    return std::chrono::duration<double, std::micro>(clock::now() - t0)
               .count() /
           n;
  };
  uint8_t seed[32] = {7}, pub[32], digest[32] = {9}, sig[64];
  pbft::ed25519_public_key(pub, seed);
  const int kSigns = 2000;
  auto t0 = clock::now();
  for (int i = 0; i < kSigns; ++i) {
    digest[0] = (uint8_t)i;
    pbft::ed25519_sign(sig, seed, digest, 32);
  }
  std::printf("ed25519_sign: %.1f us a signature\n", us_each(t0, kSigns));
  const int kVerifies = 500;
  int ok = 0;
  t0 = clock::now();
  for (int i = 0; i < kVerifies; ++i) {
    ok += pbft::ed25519_verify(pub, digest, 32, sig);
  }
  std::printf("ed25519_verify: %.1f us a verification\n",
              us_each(t0, kVerifies));
  CHECK(ok == kVerifies);
  const int kReductions = 20000;
  uint8_t wide[64], out[32] = {1};
  std::memset(wide, 0xa5, sizeof(wide));
  t0 = clock::now();
  for (int i = 0; i < kReductions; ++i) {
    std::memcpy(wide, out, 32);  // each input hangs on the last output
    pbft::ed25519_test_sc_reduce512(out, wide);
  }
  std::printf("sc_reduce512: %.3f us a 512-bit reduction mod L\n",
              us_each(t0, kReductions));
  CHECK((out[31] & 0xe0) == 0);  // under 2^253
}

void test_canonical_json() {
  auto j = pbft::Json::parse("{\"b\": 1, \"a\": \"x\\u007f\", \"c\": [1,2]}");
  CHECK(j.has_value());
  CHECK(j->dump() == "{\"a\":\"x\\u007f\",\"b\":1,\"c\":[1,2]}");
  CHECK(!pbft::Json::parse("{\"t\": 18446744073709551616}").has_value() ||
        true /* int64 overflow -> parse failure, checked via message path */);
  CHECK(!pbft::from_payload("{\"type\":\"client-request\",\"operation\":\"x\","
                            "\"timestamp\":18446744073709551616,"
                            "\"client\":\"c:1\"}"));
}

pbft::ClusterConfig test_config(std::vector<std::vector<uint8_t>>* seeds_out) {
  pbft::ClusterConfig cfg;
  for (int i = 0; i < 4; ++i) {
    std::vector<uint8_t> seed(32, (uint8_t)(i + 1));
    pbft::ReplicaIdentity ident;
    ident.replica_id = i;
    ident.host = "127.0.0.1";
    ident.port = 9000 + i;
    pbft::ed25519_public_key(ident.pubkey, seed.data());
    cfg.replicas.push_back(ident);
    seeds_out->push_back(seed);
  }
  return cfg;
}

// In-process message pump: runs replicas to quiescence through the CPU
// verifier, mirroring pbft_tpu.consensus.simulation.
struct MiniCluster {
  std::vector<pbft::Replica> replicas;
  std::vector<std::vector<pbft::Message>> inboxes;
  std::vector<pbft::ClientReply> replies;
  pbft::CpuVerifier verifier;
  std::set<int> crashed;  // crash-stop: no messages in or out
  // Sees every message on its way from `src` (-1: the test's own) to
  // `dst`, a crashed one's included.
  std::function<void(int src, int dst, const pbft::Message&)> tap;

  explicit MiniCluster(const pbft::ClusterConfig& cfg,
                       const std::vector<std::vector<uint8_t>>& seeds) {
    for (int i = 0; i < 4; ++i) {
      replicas.emplace_back(cfg, i, seeds[i].data());
      inboxes.emplace_back();
    }
  }

  void emit(int src, pbft::Actions&& acts) {
    if (crashed.count(src)) return;
    for (auto& b : acts.broadcasts) {
      for (int d = 0; d < 4; ++d) {
        if (d != src) route(d, b.msg, src);
      }
    }
    for (auto& s : acts.sends) route((int)s.dest, s.msg, src);
    for (auto& r : acts.replies) replies.push_back(r.msg);
  }

  void route(int dst, const pbft::Message& m, int src = -1) {
    if (tap) tap(src, dst, m);
    if (crashed.count(dst)) return;
    // byte-faithful hop
    auto back = pbft::from_payload(pbft::message_canonical(m));
    CHECK(back.has_value());
    inboxes[dst].push_back(*back);
  }

  bool step() {
    bool moved = false;
    for (int i = 0; i < 4; ++i) {
      std::vector<pbft::Message> q;
      q.swap(inboxes[i]);
      if (q.empty()) continue;
      moved = true;
      pbft::Actions acts;
      for (auto& m : q) acts.merge(replicas[i].receive(m));
      auto items = replicas[i].pending_items();
      if (!items.empty()) {
        acts.merge(replicas[i].deliver_verdicts(verifier.verify_batch(items)));
      }
      emit(i, std::move(acts));
    }
    return moved;
  }

  void run() {
    for (int s = 0; s < 200 && step(); ++s) {
    }
  }
};

void test_four_replica_commit() {
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  MiniCluster c(cfg, seeds);
  pbft::ClientRequest req;
  req.operation = "native";
  req.timestamp = 1;
  req.client = "127.0.0.1:9999";
  c.emit(0, c.replicas[0].on_client_request(req));
  c.run();
  CHECK(c.replies.size() == 4);
  for (auto& r : c.replies) CHECK(r.result == "awesome!");
  for (auto& r : c.replicas) CHECK(r.executed_upto() == 1);
}

void test_batched_round_native() {
  // ISSUE 4: one three-phase instance per request batch. Three requests
  // fill a batch_max_items=3 batch -> ONE sequence number, one reply per
  // request on every replica, and the digest is the batched definition.
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  cfg.batch_max_items = 3;
  MiniCluster c(cfg, seeds);
  for (int i = 0; i < 2; ++i) {
    pbft::ClientRequest req;
    req.operation = "batched-" + std::to_string(i);
    req.timestamp = 1;
    req.client = "127.0.0.1:990" + std::to_string(i);
    auto acts = c.replicas[0].on_client_request(req);
    CHECK(acts.broadcasts.empty());  // batch still open
    c.emit(0, std::move(acts));
  }
  CHECK(c.replicas[0].open_batch_size() == 2);
  // A retransmission of an OPEN-batch request claims no second slot.
  {
    pbft::ClientRequest dup;
    dup.operation = "batched-0";
    dup.timestamp = 1;
    dup.client = "127.0.0.1:9900";
    c.emit(0, c.replicas[0].on_client_request(dup));
    CHECK(c.replicas[0].open_batch_size() == 2);
  }
  pbft::ClientRequest req;
  req.operation = "batched-2";
  req.timestamp = 1;
  req.client = "127.0.0.1:9902";
  auto acts = c.replicas[0].on_client_request(req);  // seals at 3
  CHECK(acts.broadcasts.size() == 1);
  auto* pp = std::get_if<pbft::PrePrepare>(&acts.broadcasts[0].msg);
  CHECK(pp && pp->requests.size() == 3);
  CHECK(pp->digest == pbft::batch_digest_hex(pp->requests));
  c.emit(0, std::move(acts));
  c.run();
  CHECK(c.replies.size() == 4 * 3);  // one reply per request per replica
  for (auto& r : c.replicas) {
    CHECK(r.executed_upto() == 1);  // ONE instance for the whole batch
    CHECK(r.counters["rounds_executed"] == 1);
    CHECK(r.counters["executed"] == 3);
  }
  // flush_open_batch seals a partial batch (the runtime timer path).
  pbft::ClientRequest solo;
  solo.operation = "partial";
  solo.timestamp = 1;
  solo.client = "127.0.0.1:9909";
  c.emit(0, c.replicas[0].on_client_request(solo));
  CHECK(c.replicas[0].open_batch_size() == 1);
  c.emit(0, c.replicas[0].flush_open_batch());
  CHECK(c.replicas[0].open_batch_size() == 0);
  c.run();
  for (auto& r : c.replicas) CHECK(r.executed_upto() == 2);
}

void test_view_change_native() {
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  MiniCluster c(cfg, seeds);
  // Primary 0 is silent; 1-3 time out.
  for (int i = 1; i < 4; ++i) {
    auto acts = c.replicas[i].start_view_change();
    // Do not deliver to replica 0 (it is "crashed").
    for (auto& b : acts.broadcasts) {
      for (int d = 1; d < 4; ++d) {
        if (d != i) c.route(d, b.msg);
      }
    }
  }
  c.inboxes[0].clear();
  c.run();
  for (int i = 1; i < 4; ++i) {
    CHECK(c.replicas[i].view() == 1);
    CHECK(!c.replicas[i].in_view_change());
  }
  // New primary (1) orders a request in view 1.
  pbft::ClientRequest req;
  req.operation = "after-vc";
  req.timestamp = 2;
  req.client = "127.0.0.1:9999";
  c.emit(1, c.replicas[1].on_client_request(req));
  c.inboxes[0].clear();
  c.run();
  int executed = 0;
  for (int i = 1; i < 4; ++i) {
    if (c.replicas[i].executed_upto() >= 1) ++executed;
  }
  CHECK(executed == 3);
  CHECK(c.replies.size() >= 3);
}

// Sign a message exactly like Replica::sign (signable over the sig-less
// canonical form), from a raw seed — lets tests forge *correctly signed*
// Byzantine evidence.
template <typename M>
M test_sign(M msg, const std::vector<uint8_t>& seed) {
  uint8_t digest[32], sig[64];
  pbft::message_signable(pbft::Message(msg), digest);
  pbft::ed25519_sign(sig, seed.data(), digest, 32);
  msg.sig = pbft::to_hex(sig, 64);
  return msg;
}

void test_stable_digest_majority_native() {
  // Mirrors tests/test_view_change.py::
  // test_stable_digest_ignores_byzantine_first_checkpoint for the C++
  // runtime: a view-change checkpoint proof listing a correctly-signed
  // bogus-digest entry *first* must not decide the adopted state digest —
  // the 2f+1 majority does. Also pins seq_counter's low-mark floor: the
  // first post-view-change request gets seq min_s + 1.
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  MiniCluster c(cfg, seeds);
  // The majority digest commits to a REAL checkpoint payload (the new
  // state-transfer semantics: a watermark jump awaits the payload rather
  // than adopting the digest blindly).
  std::string good_chain(64, '0');
  std::string good_payload = "{\"app\":\"\",\"chain\":\"" + good_chain +
                             "\",\"replies\":[],\"seq\":10,\"timestamps\":[]}";
  uint8_t gd[32];
  pbft::blake2b_256(gd, (const uint8_t*)good_payload.data(),
                    good_payload.size());
  std::string good = pbft::to_hex(gd, 32);
  std::string evil(64, 'c');
  pbft::JsonArray proof;
  for (int i = 0; i < 4; ++i) {
    pbft::Checkpoint cp;
    cp.seq = 10;
    cp.digest = (i == 0) ? evil : good;
    cp.replica = i;
    proof.push_back(test_sign(cp, seeds[i]).to_json());
  }
  for (int i = 1; i < 4; ++i) {
    pbft::ViewChange vc;
    vc.new_view = 1;
    vc.last_stable_seq = 10;
    vc.checkpoint_proof = proof;
    vc.replica = i;
    c.route(1, pbft::Message(test_sign(vc, seeds[i])));
    c.route(2, pbft::Message(test_sign(vc, seeds[i])));
    c.route(3, pbft::Message(test_sign(vc, seeds[i])));
  }
  c.inboxes[0].clear();
  c.run();
  c.inboxes[0].clear();
  for (int i = 1; i < 4; ++i) {
    CHECK(c.replicas[i].view() == 1);
    CHECK(!c.replicas[i].in_view_change());
    CHECK(c.replicas[i].low_mark() == 10);
    // The watermark jump must NOT silently skip executions: each replica
    // awaits the payload certified by the MAJORITY digest.
    CHECK(c.replicas[i].awaiting_state());
    CHECK(c.replicas[i].executed_upto() == 0);
  }
  // A response with a tampered payload (hashing to something else — e.g.
  // what the Byzantine first entry claimed) is refused; the certified
  // payload completes recovery.
  for (int i = 1; i < 4; ++i) {
    pbft::StateResponse bad;
    bad.seq = 10;
    bad.snapshot = good_payload + " ";
    bad.replica = 0;
    c.route(i, pbft::Message(test_sign(bad, seeds[0])));
    pbft::StateResponse sp;
    sp.seq = 10;
    sp.snapshot = good_payload;
    sp.replica = 0;
    c.route(i, pbft::Message(test_sign(sp, seeds[0])));
  }
  c.inboxes[0].clear();
  c.run();
  c.inboxes[0].clear();
  for (int i = 1; i < 4; ++i) {
    CHECK(!c.replicas[i].awaiting_state());
    CHECK(c.replicas[i].executed_upto() == 10);
    CHECK(c.replicas[i].state_digest_hex() == good_chain);
  }
  // New primary 1 assigns seq 11 (= max(low_mark, min_s) + 1), not 1.
  pbft::ClientRequest req;
  req.operation = "post-vc";
  req.timestamp = 5;
  req.client = "127.0.0.1:9999";
  auto acts = c.replicas[1].on_client_request(req);
  CHECK(acts.broadcasts.size() == 1);
  auto* pp = std::get_if<pbft::PrePrepare>(&acts.broadcasts[0].msg);
  CHECK(pp && pp->seq == 11);
}

void test_state_transfer_native() {
  // A lagging replica with a STATEFUL app fetches the certified checkpoint
  // state (app snapshot + reply caches) and then serves matching replies —
  // mirrors tests/test_state_transfer.py for the C++ runtime.
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  cfg.checkpoint_interval = 4;
  MiniCluster c(cfg, seeds);
  struct AppState {
    int64_t total = 0;
  };
  std::vector<std::shared_ptr<AppState>> apps;
  for (int i = 0; i < 4; ++i) {
    auto st = std::make_shared<AppState>();
    apps.push_back(st);
    c.replicas[i].app_execute = [st](const std::string& op, int64_t) {
      st->total += std::strtoll(op.c_str(), nullptr, 10);
      return "total=" + std::to_string(st->total);
    };
    c.replicas[i].app_snapshot = [st] { return std::to_string(st->total); };
    c.replicas[i].app_restore = [st](const std::string& s) {
      st->total = s.empty() ? 0 : std::strtoll(s.c_str(), nullptr, 10);
    };
  }
  auto submit = [&](int value, int64_t ts) {
    pbft::ClientRequest req;
    req.operation = std::to_string(value);
    req.timestamp = ts;
    req.client = "127.0.0.1:9999";
    c.emit(0, c.replicas[0].on_client_request(req));
    c.run();
  };
  c.crashed.insert(3);  // replica 3 misses a stretch spanning a checkpoint
  for (int i = 0; i < 6; ++i) submit(i + 1, i + 1);
  CHECK(c.replicas[0].executed_upto() == 6);
  CHECK(c.replicas[0].low_mark() == 4);
  CHECK(c.replicas[3].executed_upto() == 0);
  c.crashed.erase(3);
  for (int i = 6; i < 10; ++i) submit(i + 1, i + 1);
  CHECK(c.replicas[3].counters["state_transfers"] >= 1);
  CHECK(!c.replicas[3].awaiting_state());
  CHECK(c.replicas[3].executed_upto() == 10);
  CHECK(c.replicas[3].state_digest_hex() == c.replicas[0].state_digest_hex());
  CHECK(apps[3]->total == apps[0]->total);
  CHECK(apps[3]->total == 55);
  // The recovered replica serves replies matching the quorum.
  size_t before = c.replies.size();
  submit(100, 11);
  int matching = 0;
  for (size_t i = before; i < c.replies.size(); ++i) {
    if (c.replies[i].result == "total=155") ++matching;
  }
  CHECK(matching == 4);
}

void test_secure_channel_native() {
  // Two-replica config with real identity keys.
  pbft::ClusterConfig cfg;
  uint8_t seeds[2][32];
  for (int i = 0; i < 2; ++i) {
    std::memset(seeds[i], i + 1, 32);
    pbft::ReplicaIdentity id;
    id.replica_id = i;
    id.host = "127.0.0.1";
    id.port = 0;
    pbft::ed25519_public_key(id.pubkey, seeds[i]);
    cfg.replicas.push_back(id);
  }
  cfg.secure = true;
  pbft::SecureChannel a(&cfg, 0, seeds[0], /*initiator=*/true, 1);
  pbft::SecureChannel b(&cfg, 1, seeds[1], /*initiator=*/false);
  auto h1 = pbft::Json::parse(a.initiator_hello());
  CHECK(h1.has_value());
  auto reply = b.on_hello(*h1);
  CHECK(reply.has_value());
  auto h2 = pbft::Json::parse(*reply);
  auto auth = a.on_hello_reply(*h2);
  CHECK(auth.has_value());
  auto ja = pbft::Json::parse(*auth);
  CHECK(b.on_auth(*ja));
  CHECK(a.established() && b.established());
  CHECK(a.peer_id() == 1 && b.peer_id() == 0);
  // Sealed frames round-trip; tampering and replay are rejected.
  std::string payload = "{\"type\":\"prepare\",\"view\":0}";
  std::string sealed = a.seal_frame(payload);
  auto opened = b.open_frame(sealed);
  CHECK(opened.has_value() && *opened == payload);
  CHECK(!b.open_frame(sealed).has_value());  // replay: counter advanced
  std::string sealed2 = a.seal_frame(payload);
  sealed2[2] ^= 0x10;
  CHECK(!b.open_frame(sealed2).has_value());
  // Version mismatch rejected with a clear error.
  pbft::SecureChannel c(&cfg, 1, seeds[1], /*initiator=*/false);
  auto bad = pbft::Json::parse(
      "{\"type\":\"hello\",\"ver\":\"pbft-tpu/9.9.9\",\"node\":0,\"eph\":\"" +
      std::string(64, '0') + "\"}");
  CHECK(bad.has_value());
  CHECK(!c.on_hello(*bad).has_value());
  CHECK(c.error().find("version mismatch") != std::string::npos);
  // Plaintext hello into a secure responder rejected.
  pbft::SecureChannel d(&cfg, 1, seeds[1], /*initiator=*/false);
  auto plain = pbft::Json::parse(pbft::SecureChannel::plain_hello(0));
  CHECK(!d.on_hello(*plain).has_value());
  CHECK(d.error().find("plaintext peer rejected") != std::string::npos);
}

// --- ISSUE 37: a verify batch is a SPAN of the inbox ------------------------
//
// The runtime ships the span behind a batch before it works through that
// batch's verdicts, so several spans may be cut (pending_items) before the
// first is delivered (deliver_verdicts). Order is the safety property.

pbft::Prepare span_prepare(int64_t seq, int64_t from,
                           const std::vector<std::vector<uint8_t>>& seeds) {
  pbft::Prepare p;
  p.view = 0;
  p.seq = seq;
  p.digest = std::string(64, 'a');
  p.replica = from;
  return test_sign(p, seeds[from]);
}

void test_span_delivered_while_next_on_wire() {
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  pbft::Replica r(cfg, 1, seeds[1].data());
  pbft::CpuVerifier cpu;
  r.receive(pbft::Message(span_prepare(1, 2, seeds)));
  r.receive(pbft::Message(span_prepare(1, 3, seeds)));
  auto span1 = r.pending_items();
  CHECK(span1.size() == 2);
  CHECK(r.unlaunched_count() == 0 && r.pending_count() == 2);
  // Span 1's verdicts are in hand; what arrived behind it goes on the wire
  // BEFORE they are applied, and holds none of span 1's entries.
  auto third = span_prepare(2, 2, seeds);
  r.receive(pbft::Message(third));
  CHECK(r.unlaunched_count() == 1);
  auto span2 = r.pending_items();
  CHECK(span2.size() == 1);
  uint8_t want[32];
  pbft::message_signable(pbft::Message(third), want);
  CHECK(std::memcmp(span2[0].msg, want, 32) == 0);
  CHECK(r.pending_items().empty());  // nothing is cut twice
  // A message the replica gets while both are undelivered (a self-
  // delivered vote, say) queues behind span 2.
  r.receive(pbft::Message(span_prepare(2, 3, seeds)));
  CHECK(r.unlaunched_count() == 1 && r.pending_count() == 4);
  r.deliver_verdicts(cpu.verify_batch(span1));
  CHECK(r.counters["prepares_accepted"] == 2);
  CHECK(r.pending_count() == 2 && r.unlaunched_count() == 1);
  r.deliver_verdicts(cpu.verify_batch(span2));
  CHECK(r.counters["prepares_accepted"] == 3);
  CHECK(r.pending_count() == 1 && r.unlaunched_count() == 1);
  auto span3 = r.pending_items();
  CHECK(span3.size() == 1);
  r.deliver_verdicts(cpu.verify_batch(span3));
  CHECK(r.counters["prepares_accepted"] == 4);
  CHECK(r.counters["sig_verified"] == 4 && r.counters["sig_rejected"] == 0);
  CHECK(r.pending_count() == 0 && r.unlaunched_count() == 0);
}

void test_pre_authenticated_waits_for_span_on_wire() {
  // ISSUE 14's wedge, with spans: a MAC-accepted entry drains only up to
  // the first entry that awaits a verdict — also when that entry's span
  // is on the wire and its verdict simply has not come back yet.
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  pbft::Replica r(cfg, 1, seeds[1].data());
  r.receive(pbft::Message(span_prepare(1, 2, seeds)));
  auto span1 = r.pending_items();
  // Behind span 1: a pre-authenticated entry, then a signed one (span 2,
  // on the wire), then two more pre-authenticated ones.
  r.receive_authenticated(pbft::Message(span_prepare(2, 2, seeds)));
  r.receive(pbft::Message(span_prepare(1, 3, seeds)));
  auto span2 = r.pending_items();
  CHECK(span1.size() == 1 && span2.size() == 1);
  r.receive_authenticated(pbft::Message(span_prepare(2, 3, seeds)));
  r.receive_authenticated(pbft::Message(span_prepare(3, 2, seeds)));
  CHECK(r.counters["mac_verified"] == 3);
  CHECK(r.counters["prepares_accepted"] == 0);  // all queued, none overtook
  CHECK(r.unlaunched_count() == 2);
  CHECK(r.pending_items().empty());  // they await no verdict: no launch
  r.deliver_verdicts({1});
  // Span 1's entry and the pre-authenticated one right behind it; the two
  // behind the on-the-wire entry stay where they are.
  CHECK(r.counters["prepares_accepted"] == 2);
  CHECK(r.pending_count() == 3);
  r.deliver_verdicts({1});
  CHECK(r.counters["prepares_accepted"] == 5);
  CHECK(r.pending_count() == 0 && r.unlaunched_count() == 0);
  // Inbox empty again: the fast path dispatches at once.
  r.receive_authenticated(pbft::Message(span_prepare(3, 3, seeds)));
  CHECK(r.counters["prepares_accepted"] == 6);
}

void test_rejected_signature_in_kept_span() {
  std::vector<std::vector<uint8_t>> seeds;
  auto cfg = test_config(&seeds);
  pbft::Replica r(cfg, 1, seeds[1].data());
  pbft::CpuVerifier cpu;
  auto forged = span_prepare(1, 2, seeds);
  forged.digest = std::string(64, 'b');  // signed over another digest
  r.receive(pbft::Message(forged));
  r.receive(pbft::Message(span_prepare(1, 3, seeds)));
  auto span1 = r.pending_items();
  r.receive(pbft::Message(span_prepare(2, 2, seeds)));
  auto span2 = r.pending_items();
  auto v1 = cpu.verify_batch(span1);
  CHECK(v1 == (std::vector<uint8_t>{0, 1}));
  r.deliver_verdicts(v1);
  // The forgery is dropped, the entry behind it in the span dispatched,
  // and span 2's entry still waits for its own verdict.
  CHECK(r.counters["sig_rejected"] == 1 && r.counters["sig_verified"] == 1);
  CHECK(r.counters["prepares_accepted"] == 1);
  CHECK(r.pending_count() == 1);
  r.deliver_verdicts(cpu.verify_batch(span2));
  CHECK(r.counters["prepares_accepted"] == 2);
  CHECK(r.pending_count() == 0);
}


void test_batch_verify_rlc() {
  // The RLC + Pippenger batch path must agree with per-item verify:
  // honest windows all-accept, corrupted items are isolated by the
  // bisect (sizes straddle the RLC threshold and the window widths).
  for (size_t n : {0, 1, 3, 8, 40, 200}) {
    std::vector<uint8_t> pubs(32 * n), msgs(32 * n), sigs(64 * n), out(n);
    for (size_t i = 0; i < n; ++i) {
      uint8_t seed[32];
      std::memset(seed, (int)(i + 1), 32);
      std::memset(msgs.data() + 32 * i, (int)(0xA0 ^ i), 32);
      pbft::ed25519_public_key(pubs.data() + 32 * i, seed);
      pbft::ed25519_sign(sigs.data() + 64 * i, seed, msgs.data() + 32 * i, 32);
    }
    // Corrupt every 7th item (S byte), plus one pubkey (decompress-fail
    // pre-check) when the batch is big enough.
    std::set<size_t> bad;
    for (size_t i = 0; i < n; i += 7) {
      sigs[64 * i + 40] ^= 0x5A;
      bad.insert(i);
    }
    if (n > 10) {
      pubs[32 * 9] ^= 0xFF;
      pubs[32 * 9 + 31] ^= 0x80;
      bad.insert(9);
    }
    pbft::ed25519_verify_batch(pubs.data(), msgs.data(), sigs.data(), n,
                               out.data());
    for (size_t i = 0; i < n; ++i) {
      bool expect = !bad.count(i);
      CHECK(out[i] == (expect ? 1 : 0));
      CHECK(pbft::ed25519_verify(pubs.data() + 32 * i, msgs.data() + 32 * i,
                                 32, sigs.data() + 64 * i) == expect);
    }
  }
}

void test_verify_pool_native() {
  // Pool lifecycle: construct/verify/destroy across widths (ASAN-friendly:
  // every worker joins in the destructor, no sleeps), pooled verdicts
  // identical to the serial path, stats accounting, and the entropy-
  // exhaustion fallback (RLC disabled -> per-item, honest items still
  // accepted).
  const size_t n = (size_t)pbft::kEd25519RlcWindowItems + 40;
  std::vector<uint8_t> pubs(32 * n), msgs(32 * n), sigs(64 * n);
  for (size_t i = 0; i < n; ++i) {
    uint8_t seed[32];
    std::memset(seed, (int)(i % 250 + 1), 32);
    std::memset(msgs.data() + 32 * i, (int)(0xA0 ^ (i & 0xFF)), 32);
    pbft::ed25519_public_key(pubs.data() + 32 * i, seed);
    pbft::ed25519_sign(sigs.data() + 64 * i, seed, msgs.data() + 32 * i, 32);
  }
  // Corruption at both sides of the window boundary and in each window.
  std::set<size_t> bad = {0, pbft::kEd25519RlcWindowItems - 1,
                          pbft::kEd25519RlcWindowItems, n - 1, 17};
  for (size_t i : bad) sigs[64 * i + 40] ^= 0x5A;
  std::vector<uint8_t> serial(n);
  pbft::ed25519_verify_batch(pubs.data(), msgs.data(), sigs.data(), n,
                             serial.data());
  for (size_t i = 0; i < n; ++i) CHECK(serial[i] == (bad.count(i) ? 0 : 1));
  for (int threads : {1, 2, 3}) {
    pbft::VerifyPool pool(threads);
    CHECK(pool.threads() == threads);
    std::vector<uint8_t> out(n);
    pool.verify(pubs.data(), msgs.data(), sigs.data(), n, out.data());
    CHECK(out == serial);
    auto s = pool.stats();
    CHECK(s.threads == threads);
    CHECK(s.batches == 1 && s.windows == 2 && s.items == (int64_t)n);
    CHECK(s.wall_seconds > 0 && s.busy_seconds > 0);
    CHECK(s.last_window_items == (int64_t)pbft::kEd25519RlcWindowItems);
  }
  // Entropy exhaustion: fast path off, honest items still verify.
  pbft::ed25519_test_force_entropy_exhaustion(true);
  std::vector<uint8_t> out(n);
  pbft::VerifyPool pool(2);
  pool.verify(pubs.data(), msgs.data(), sigs.data(), n, out.data());
  pbft::ed25519_test_force_entropy_exhaustion(false);
  CHECK(out == serial);
  // Metrics export: the pool gauges/histogram are registered and render
  // under the manifest names (schema parity with trace_schema.py).
  pbft::Metrics m;
  m.enabled = true;
  m.set_gauge("pbft_verify_pool_threads", 2);
  m.set_gauge("pbft_verify_pool_queue_depth", 2);
  m.set_gauge("pbft_verify_pool_utilization", 0.5);
  m.observe("pbft_verify_pool_window_size", 256);
  std::string text = m.render_prometheus("0");
  CHECK(text.find("# TYPE pbft_verify_pool_threads gauge") !=
        std::string::npos);
  CHECK(text.find("pbft_verify_pool_threads{replica=\"0\"} 2") !=
        std::string::npos);
  CHECK(text.find("pbft_verify_pool_utilization{replica=\"0\"} 0.5") !=
        std::string::npos);
  CHECK(text.find("pbft_verify_pool_window_size_bucket{replica=\"0\","
                  "le=\"256\"} 1") != std::string::npos);
  CHECK(text.find("pbft_verify_pool_window_size_count{replica=\"0\"} 1") !=
        std::string::npos);
}

void test_remote_verifier_async() {
  // Drive the async verifier protocol against a socketpair standing in
  // for the service: request framing, partial-verdict reads, and the
  // mid-batch-EOF failure signal the event loop's CPU safety net keys on.
  int sv[2];
  CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
  pbft::RemoteVerifier rv("/nonexistent-but-unused");
  rv.adopt_fd_for_test(sv[0]);

  std::vector<pbft::VerifyItem> items(3);
  for (int i = 0; i < 3; ++i) {
    std::memset(items[i].pub, i + 1, 32);
    std::memset(items[i].msg, i + 9, 32);
    std::memset(items[i].sig, i + 17, 64);
  }
  CHECK(rv.begin_batch(items));
  CHECK(rv.async_fd() == sv[0]);
  // Duplicate dispatch while in flight is refused.
  CHECK(!rv.begin_batch(items));

  // Service side: whole request arrives framed as u32be count + 128 B/item.
  uint8_t req[4 + 3 * 128];
  CHECK(read(sv[1], req, sizeof(req)) == (ssize_t)sizeof(req));
  CHECK(req[3] == 3 && req[0] == 0);
  CHECK(req[4] == 1 && req[4 + 128] == 2);  // first pub byte per item

  std::vector<uint8_t> verdicts;
  bool failed = true;
  // Nothing written yet: poll_result must report "still in flight".
  CHECK(!rv.poll_result(&verdicts, &failed));
  // Partial verdicts: still in flight.
  uint8_t part1[1] = {1};
  CHECK(write(sv[1], part1, 1) == 1);
  CHECK(!rv.poll_result(&verdicts, &failed));
  uint8_t part2[2] = {0, 1};
  CHECK(write(sv[1], part2, 2) == 2);
  CHECK(rv.poll_result(&verdicts, &failed));
  CHECK(!failed);
  CHECK(verdicts == (std::vector<uint8_t>{1, 0, 1}));

  // Second batch: EOF mid-flight flags failure (fallback's cue).
  CHECK(rv.begin_batch(items));
  CHECK(read(sv[1], req, sizeof(req)) == (ssize_t)sizeof(req));
  ::close(sv[1]);
  CHECK(rv.poll_result(&verdicts, &failed));
  CHECK(failed);

  // Wedge-deadline cancellation (net.cc check_verify_deadline): the
  // transport drops — even with partial verdicts already received — so a
  // late reply cannot mis-pair with the next batch, and the verifier is
  // immediately reusable.
  int sv2[2];
  CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv2) == 0);
  rv.adopt_fd_for_test(sv2[0]);
  CHECK(rv.begin_batch(items));
  uint8_t part3[1] = {1};
  CHECK(write(sv2[1], part3, 1) == 1);
  CHECK(!rv.poll_result(&verdicts, &failed));  // partial: still in flight
  rv.cancel_inflight();
  CHECK(rv.async_fd() == -1);  // no longer polled by the event loop
  ::close(sv2[1]);
}

void test_remote_verifier_readiness() {
  // The verify-service readiness handshake (ISSUE 7): parse the 8-byte
  // status record, defer to the fallback while warming, use the service
  // once ready, and assume a silent pre-handshake service is ready.
  ::setenv("PBFT_VERIFY_PROBE_MS", "50", 1);
  auto pack = [](uint8_t state, uint16_t devices, uint16_t warmed) {
    return std::vector<uint8_t>{'V',
                                'S',
                                1,
                                state,
                                (uint8_t)(devices >> 8),
                                (uint8_t)devices,
                                (uint8_t)(warmed >> 8),
                                (uint8_t)warmed};
  };
  {
    int sv[2];
    CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
    pbft::RemoteVerifier rv("/unused");
    rv.adopt_fd_for_test(sv[0]);
    auto warming = pack(0, 8, 5);
    CHECK(write(sv[1], warming.data(), warming.size()) == 8);
    CHECK(rv.probe_status_for_test());
    CHECK(rv.service_state() ==
          pbft::RemoteVerifier::ServiceState::kWarming);
    CHECK(rv.service_devices() == 8);
    // Warming -> begin_batch refuses (the event loop's CPU safety net
    // carries the batch); the embedded reprobe times out against the
    // silent socketpair and the connection drops.
    std::vector<pbft::VerifyItem> items(1);
    std::memset(items[0].pub, 1, 32);
    CHECK(!rv.begin_batch(items));
    CHECK(rv.async_fd() == -1);
    ::close(sv[1]);
  }
  {
    int sv[2];
    CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
    pbft::RemoteVerifier rv("/unused");
    rv.adopt_fd_for_test(sv[0]);
    auto ready = pack(1, 4, 5);
    CHECK(write(sv[1], ready.data(), ready.size()) == 8);
    CHECK(rv.probe_status_for_test());
    CHECK(rv.service_state() == pbft::RemoteVerifier::ServiceState::kReady);
    CHECK(rv.service_devices() == 4);
    // Ready -> batches ship (the probe's own 4-byte request is still in
    // the socketpair; drain it before the batch frame).
    std::vector<pbft::VerifyItem> items(1);
    std::memset(items[0].pub, 7, 32);
    CHECK(rv.begin_batch(items));
    uint8_t buf[4 + 4 + 128];  // probe + framed 1-item batch
    CHECK(read(sv[1], buf, sizeof(buf)) == (ssize_t)sizeof(buf));
    CHECK(buf[7] == 1 && buf[8] == 7);
    ::close(sv[1]);
  }
  {
    // cpu-only: usable (a CPU service still coalesces colocated daemons).
    int sv[2];
    CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
    pbft::RemoteVerifier rv("/unused");
    rv.adopt_fd_for_test(sv[0]);
    auto cpu = pack(2, 0, 0);
    CHECK(write(sv[1], cpu.data(), cpu.size()) == 8);
    CHECK(rv.probe_status_for_test());
    CHECK(rv.service_state() ==
          pbft::RemoteVerifier::ServiceState::kCpuOnly);
    std::vector<pbft::VerifyItem> items(1);
    CHECK(rv.begin_batch(items));
    ::close(sv[1]);
  }
  {
    // Legacy service: no status reply -> the target is remembered as
    // pre-handshake (state reads ready) but the probe call must return
    // FALSE — the timed-out probe is still outstanding on this stream,
    // and a slow-but-modern service answering it late would mis-pair 8
    // status bytes with the next batch's verdict bytes (the sanitizer
    // matrix's race_stress drove this: 'V','S',... surfacing as
    // signature verdicts). ensure_connected re-dials legacy targets on
    // a clean stream instead. Garbage status -> probe fails outright.
    int sv[2];
    CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
    pbft::RemoteVerifier rv("/unused");
    rv.adopt_fd_for_test(sv[0]);
    CHECK(!rv.probe_status_for_test(/*allow_legacy=*/true));
    CHECK(rv.service_state() == pbft::RemoteVerifier::ServiceState::kReady);
    uint8_t garbage[8] = {'X', 'X', 9, 9, 0, 0, 0, 0};
    CHECK(write(sv[1], garbage, 8) == 8);
    CHECK(!rv.probe_status_for_test());
    ::close(sv[1]);
  }
  {
    // Regression (ISSUE 8, found by race_stress under TSan timing): a
    // status reply that lands AFTER the probe deadline must never be
    // read as verdict bytes. The timed-out stream above was the only
    // path that could reuse a probe-dirty connection; pin that the
    // stream is not trusted (probe returns false) even when the late
    // reply is already sitting in the socket buffer by the next read.
    int sv[2];
    CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
    pbft::RemoteVerifier rv("/unused");
    rv.adopt_fd_for_test(sv[0]);
    CHECK(!rv.probe_status_for_test(/*allow_legacy=*/true));  // times out
    auto late = pack(1, 1, 5);  // the slow service finally answers
    CHECK(write(sv[1], late.data(), late.size()) == 8);
    // The caller's contract after a false probe is drop + re-dial; a
    // batch must NOT be shipped on this stream. (Before the fix the
    // probe returned true here and the 8 late bytes became the first 8
    // "verdicts" of the next batch.)
    ::close(sv[1]);
  }
  ::unsetenv("PBFT_VERIFY_PROBE_MS");
}

}  // namespace

// --- ISSUE 10: epoll-ET loop vs the poll() fallback ------------------------

// A listening loopback socket on `port` (0: any free one).
int listen_on_port(int port) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  pbft::tune_listen_socket(fd);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons((uint16_t)port);
  if (::bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0 ||
      ::listen(fd, 64) != 0) {
    ::close(fd);
    return -1;
  }
  return fd;
}

// The port at this end of a socket, or at its far end.
int socket_port(int fd, bool far_end) {
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  const int rc = far_end ? ::getpeername(fd, (sockaddr*)&addr, &len)
                         : ::getsockname(fd, (sockaddr*)&addr, &len);
  return rc == 0 ? ntohs(addr.sin_port) : -1;
}

int parity_listen_ephemeral(int* port_out) {
  const int fd = listen_on_port(0);
  if (fd >= 0) *port_out = socket_port(fd, /*far_end=*/false);
  return fd;
}

// The hello a gateway opens its link to a replica with, framed.
std::string gateway_hello_frame() {
  auto hello = pbft::Json::parse(pbft::SecureChannel::plain_hello(-1));
  CHECK(hello.has_value());
  pbft::JsonObject ho = hello->as_object();
  ho["role"] = pbft::Json("gateway");
  return pbft::frame_payload(pbft::Json(ho).dump());
}

// Four identities on loopback ports that were free a moment ago (seed of
// replica i: 32 bytes of seed_base + i).
pbft::ClusterConfig loopback_config(uint8_t seed_base, int* ports,
                                    std::vector<std::vector<uint8_t>>* seeds) {
  pbft::ClusterConfig cfg;
  int hold[4];
  for (int i = 0; i < 4; ++i) {
    hold[i] = parity_listen_ephemeral(&ports[i]);
    CHECK(hold[i] >= 0);
    std::vector<uint8_t> seed(32, (uint8_t)(seed_base + i));
    pbft::ReplicaIdentity ident;
    ident.replica_id = i;
    ident.host = "127.0.0.1";
    ident.port = ports[i];
    pbft::ed25519_public_key(ident.pubkey, seed.data());
    cfg.replicas.push_back(ident);
    seeds->push_back(seed);
  }
  for (int i = 0; i < 4; ++i) ::close(hold[i]);
  return cfg;
}

// Send `req` to the replicas in turn (a retransmission every 400 ms, as a
// client would) until `want` DISTINCT replicas have dialed back with a
// reply, or `seconds` pass; returns who replied. Every replica dials back
// once it has executed, so waiting for all four is waiting for the whole
// cluster: a round that stops its servers at the client's quorum (f+1)
// and then holds each to executed_upto() >= 1 fails on a loaded host,
// where the last replica's commits are still in flight (the one red test
// of the driver's 6-worker run).
std::set<std::string> await_repliers(int reply_fd, const int* ports,
                                     const std::string& req, size_t want,
                                     int seconds) {
  std::set<std::string> repliers;
  auto deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(seconds);
  int attempt = 0;
  while (repliers.size() < want &&
         std::chrono::steady_clock::now() < deadline) {
    int fd = pbft::dial_tcp("127.0.0.1:" +
                            std::to_string(ports[attempt++ % 4]));
    if (fd >= 0) {
      (void)!::send(fd, req.data(), req.size(), MSG_NOSIGNAL);
      ::close(fd);
    }
    auto retry_at =
        std::chrono::steady_clock::now() + std::chrono::milliseconds(400);
    while (repliers.size() < want &&
           std::chrono::steady_clock::now() < retry_at) {
      pollfd pfd{reply_fd, POLLIN, 0};
      if (::poll(&pfd, 1, 50) <= 0) continue;
      int cfd = ::accept(reply_fd, nullptr, nullptr);
      if (cfd < 0) continue;
      char buf[512];
      ssize_t got = ::recv(cfd, buf, sizeof(buf) - 1, 0);
      ::close(cfd);
      if (got <= 0) continue;
      const std::string reply(buf, (size_t)got);
      const size_t at = reply.find("\"replica\":");
      if (at != std::string::npos) {
        repliers.insert(
            reply.substr(at + 10, reply.find_first_of(",}", at) - at - 10));
      }
    }
  }
  return repliers;
}

// A sample's value in Prometheus text ("<name>{replica="r"} <value>").
double prometheus_sample(const std::string& text, const std::string& name) {
  const size_t at = text.find("\n" + name + "{");
  if (at == std::string::npos) return -1;
  const size_t sp = text.find("} ", at);
  return sp == std::string::npos ? -1 : std::atof(text.c_str() + sp + 2);
}

// One real-socket 4-replica round: client request in, f+1 dial-back
// replies observed, on whichever readiness backend the environment
// selects. The PBFT_NET_POLL=1 arm proves the incrementally-maintained
// poll() fallback is behaviorally identical to edge-triggered epoll.
// Every replica runs with metrics and a WAL, so the round also pins the
// verify-trip histograms: one inbox-wait observation per verify batch
// (blocking branch here; tests/test_verify_spans.py drives the async
// one), one flush observation per WAL flush that had records pending.
void parity_round(const char* want_backend) {
  int ports[4];
  std::vector<std::vector<uint8_t>> seeds;
  pbft::ClusterConfig cfg = loopback_config(41, ports, &seeds);
  const char* tmp = std::getenv("TMPDIR");
  std::string wal_dir = std::string(tmp ? tmp : "/tmp") + "/pbft-parity-wal-XXXXXX";
  CHECK(::mkdtemp(wal_dir.data()) != nullptr);
  std::vector<std::unique_ptr<pbft::ReplicaServer>> servers;
  for (int i = 0; i < 4; ++i) {
    servers.push_back(std::make_unique<pbft::ReplicaServer>(
        cfg, i, seeds[i].data(), std::make_unique<pbft::CpuVerifier>()));
    servers[i]->metrics().enabled = true;
    CHECK(servers[i]->enable_wal(wal_dir));
    CHECK(servers[i]->start());
    CHECK(std::string(servers[i]->net_backend()) == want_backend);
  }
  std::vector<std::thread> loops;
  for (int i = 0; i < 4; ++i) {
    loops.emplace_back([srv = servers[i].get()] { srv->run(); });
  }
  int reply_port = 0;
  int reply_fd = parity_listen_ephemeral(&reply_port);
  CHECK(reply_fd >= 0);
  const std::string reply_addr = "127.0.0.1:" + std::to_string(reply_port);
  const std::string req =
      "{\"type\":\"client-request\",\"operation\":\"backend\","
      "\"timestamp\":1,\"client\":\"" + reply_addr + "\"}\n";
  const auto repliers = await_repliers(reply_fd, ports, req, 4, 30);
  CHECK(repliers.size() >= 2);  // f+1 distinct dial-backs: the client's quorum
  CHECK(repliers.size() == 4);
  for (auto& s : servers) s->stop();
  for (auto& t : loops) t.join();
  for (auto& s : servers) CHECK(s->replica().executed_upto() >= 1);
  for (int i = 0; i < 4; ++i) {
    const std::string text = "\n" + servers[i]->metrics().render_prometheus("r");
    const double batches = prometheus_sample(text, "pbft_verify_batches_total");
    CHECK(batches >= 1);
    CHECK(prometheus_sample(text, "pbft_verify_inbox_wait_seconds_count") ==
          batches);
    CHECK(prometheus_sample(text, "pbft_verify_inbox_wait_seconds_sum") >= 0);
    const double flushes = prometheus_sample(text, "pbft_wal_flush_seconds_count");
    CHECK(flushes >= 1);
    CHECK(flushes <= prometheus_sample(text, "pbft_wal_appends_total"));
    ::unlink((wal_dir + "/replica-" + std::to_string(i) + ".wal").c_str());
  }
  ::rmdir(wal_dir.c_str());
  ::close(reply_fd);
}

void test_net_backend_parity() {
  ::setenv("PBFT_NET_POLL", "1", 1);
  parity_round("poll");
  ::unsetenv("PBFT_NET_POLL");
#ifdef __linux__
  parity_round("epoll-et");
#endif
}

// ISSUE 13: the multi-core front end (net_threads > 1: SO_REUSEPORT
// accept sharding, loop shards + crypto pipelines + consensus thread)
// must drive a real-socket 4-replica cluster to the SAME executed state
// as the classic single loop. Two sequential requests per arm; returns
// the cluster-wide max executed_upto after a clean stop.
int64_t multicore_round(int net_threads, bool fastpath_mac = false,
                        bool tentative = false) {
  int ports[4];
  std::vector<std::vector<uint8_t>> seeds;
  pbft::ClusterConfig cfg = loopback_config(73, ports, &seeds);
  cfg.net_threads = net_threads;
  if (fastpath_mac) cfg.fastpath = "mac";
  cfg.tentative = tentative;
  std::vector<std::unique_ptr<pbft::ReplicaServer>> servers;
  for (int i = 0; i < 4; ++i) {
    servers.push_back(std::make_unique<pbft::ReplicaServer>(
        cfg, i, seeds[i].data(), std::make_unique<pbft::CpuVerifier>()));
    CHECK(servers[i]->start());
  }
  std::vector<std::thread> loops;
  for (int i = 0; i < 4; ++i) {
    loops.emplace_back([srv = servers[i].get()] { srv->run(); });
  }
  int reply_port = 0;
  int reply_fd = parity_listen_ephemeral(&reply_port);
  CHECK(reply_fd >= 0);
  const std::string reply_addr = "127.0.0.1:" + std::to_string(reply_port);
  for (int ts = 1; ts <= 2; ++ts) {
    const std::string req =
        "{\"type\":\"client-request\",\"operation\":\"mc-" +
        std::to_string(ts) + "\",\"timestamp\":" + std::to_string(ts) +
        ",\"client\":\"" + reply_addr + "\"}\n";
    const auto repliers = await_repliers(reply_fd, ports, req, 4, 30);
    CHECK(repliers.size() >= 2);  // f+1 distinct dial-backs per request
    CHECK(repliers.size() == 4);  // ...and every replica executed it
    if (repliers.size() != 4) {
      std::string who;
      for (const auto& r : repliers) who += r + " ";
      std::fprintf(stderr, "  net_threads=%d mac=%d tentative=%d ts=%d replied: %s\n",
                   net_threads, (int)fastpath_mac, (int)tentative, ts, who.c_str());
    }
  }
  // Let the trailing commits land everywhere before the stop (a tentative
  // reply leaves at PREPARED, ahead of the commits that promote it).
  std::this_thread::sleep_for(std::chrono::seconds(2));
  for (auto& s : servers) s->stop();
  for (auto& t : loops) t.join();
  int64_t max_executed = 0;
  for (auto& s : servers) {
    max_executed = std::max(max_executed, s->replica().executed_upto());
    CHECK(s->replica().executed_upto() >= 1);
    if (fastpath_mac) {
      // The fast path actually carried the round: MAC-accepted frames
      // dispatched without the verify queue on every replica.
      CHECK(s->replica().counters["mac_verified"] > 0);
    }
    if (tentative) {
      // Commits promoted every tentative execution: the floor caught up.
      CHECK(s->replica().committed_upto() == s->replica().executed_upto());
    }
  }
  ::close(reply_fd);
  return max_executed;
}

// ISSUE 40: the front end under a BURST. The test is the gateway: one
// persistent link a replica (a role=gateway hello, then framed raw-JSON
// payloads both ways), 1,024 requests of 32 clients written to the
// primary's link in one go, batches of 32. Up to PR 39 the sharded front
// end stopped after one or two sequence numbers of such a burst
// (WakeFd::drain cleared its flag before it emptied the fd, so its read()
// could swallow the write of a producer that had seen the flag cleared:
// the flag then stood set over an empty fd, every later wake() returned
// early, and the consensus thread was never handed the rest). Every
// request must come back from all four replicas, on one history.
void multicore_burst(int net_threads) {
  constexpr int kClients = 32, kEach = 32, kAll = kClients * kEach;
  int ports[4];
  std::vector<std::vector<uint8_t>> seeds;
  pbft::ClusterConfig cfg = loopback_config(91, ports, &seeds);
  cfg.net_threads = net_threads;
  cfg.batch_max_items = 32;
  cfg.batch_flush_us = 2000;
  std::vector<std::unique_ptr<pbft::ReplicaServer>> servers;
  for (int i = 0; i < 4; ++i) {
    servers.push_back(std::make_unique<pbft::ReplicaServer>(
        cfg, i, seeds[i].data(), std::make_unique<pbft::CpuVerifier>()));
    servers[i]->metrics().enabled = true;
    CHECK(servers[i]->start());
  }
  std::vector<std::thread> loops;
  for (int i = 0; i < 4; ++i) {
    loops.emplace_back([srv = servers[i].get()] { srv->run(); });
  }
  const std::string hello_frame = gateway_hello_frame();
  int links[4];
  for (int i = 0; i < 4; ++i) {
    links[i] = pbft::dial_tcp("127.0.0.1:" + std::to_string(ports[i]));
    CHECK(links[i] >= 0);
    CHECK(::send(links[i], hello_frame.data(), hello_frame.size(),
                 MSG_NOSIGNAL) == (ssize_t)hello_frame.size());
  }
  // A send() a request, as a gateway forwards them: the primary's shard
  // reads them in many small pieces, its pipeline pushes and wakes the
  // consensus thread while that thread is draining, which is where the
  // wake was lost.
  for (int ts = 1; ts <= kEach; ++ts) {
    for (int c = 0; c < kClients; ++c) {
      const std::string frame = pbft::frame_payload(
          "{\"type\":\"client-request\",\"operation\":\"b" +
          std::to_string(c) + "-" + std::to_string(ts) +
          "\",\"timestamp\":" + std::to_string(ts) +
          ",\"client\":\"gw/burst-" + std::to_string(c) + "\"}");
      CHECK(::send(links[0], frame.data(), frame.size(), MSG_NOSIGNAL) ==
            (ssize_t)frame.size());
    }
  }
  // (client, timestamp) -> the replicas that answered, read off all four
  // links (each replica answers on the link its gateway route names, or
  // fans out over its one gateway link).
  std::map<std::pair<std::string, int64_t>, std::set<int64_t>> answered;
  size_t complete = 0;
  std::string rbuf[4];
  const auto deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(60);
  while (complete < (size_t)kAll &&
         std::chrono::steady_clock::now() < deadline) {
    pollfd pfds[4];
    for (int i = 0; i < 4; ++i) pfds[i] = pollfd{links[i], POLLIN, 0};
    if (::poll(pfds, 4, 100) <= 0) continue;
    for (int i = 0; i < 4; ++i) {
      if (!(pfds[i].revents & (POLLIN | POLLHUP))) continue;
      char buf[65536];
      ssize_t got = ::recv(links[i], buf, sizeof(buf), 0);
      if (got <= 0) continue;
      rbuf[i].append(buf, (size_t)got);
      for (;;) {
        if (rbuf[i].size() < 4) break;
        const uint32_t len = ((uint32_t)(uint8_t)rbuf[i][0] << 24) |
                             ((uint32_t)(uint8_t)rbuf[i][1] << 16) |
                             ((uint32_t)(uint8_t)rbuf[i][2] << 8) |
                             (uint32_t)(uint8_t)rbuf[i][3];
        if (rbuf[i].size() < 4 + (size_t)len) break;
        auto j = pbft::Json::parse(rbuf[i].substr(4, len));
        rbuf[i].erase(0, 4 + (size_t)len);
        if (!j) continue;
        const pbft::Json* t = j->find("type");
        if (!t || !t->is_string() || t->as_string() != "client-reply") continue;
        auto& who = answered[{j->find("client")->as_string(),
                              j->find("timestamp")->as_int()}];
        who.insert(j->find("replica")->as_int());
        if (who.size() == 4) ++complete;
      }
    }
  }
  CHECK(complete == (size_t)kAll);
  if (complete != (size_t)kAll) {
    std::fprintf(stderr, "  net_threads=%d: %zu of %d requests answered by all four\n",
                 net_threads, complete, kAll);
  }
  for (auto& s : servers) s->stop();
  for (auto& t : loops) t.join();
  for (int i = 0; i < 4; ++i) ::close(links[i]);
  std::set<int64_t> upto;
  std::set<std::string> chains;
  for (auto& s : servers) {
    upto.insert(s->replica().executed_upto());
    chains.insert(s->replica().committed_chain_hex());
    CHECK(s->replica().view() == 0);
    CHECK(s->replica().counters["executed"] == kAll);
  }
  CHECK(upto.size() == 1 && *upto.begin() >= kAll / 32);
  CHECK(chains.size() == 1);
  if (net_threads > 1) {
    // Nothing was lost at a thread boundary, and the front-end threads'
    // clocks ran: /status says so by kind and by thread.
    auto st = pbft::Json::parse(servers[0]->metrics_json());
    CHECK(st.has_value());
    const pbft::Json* dropped = st->find("shard_dropped");
    CHECK(dropped != nullptr);
    for (const char* kind : {"pipeline", "inbox", "replies"}) {
      CHECK(dropped->find(kind)->as_int() == 0);
    }
    CHECK(st->find("net_threads")->as_int() == net_threads);
    CHECK((int)st->find("shard_us")->as_array().size() == net_threads);
    CHECK((int)st->find("pipe_us")->as_array().size() == net_threads);
  }
}

// The hand-off's one primitive, alone: a producer that pushes and wakes
// every microsecond or so (a pipeline parsing a frame between two pushes),
// a consumer that waits on the fd, drains it and then the queue, each on
// a CPU of its own where the process may use two. A wake is lost when the
// consumer's wait times out over a queue that holds something. With
// drain() clearing its flag BEFORE it empties the fd (the order up to PR
// 39) that happens within the first cycles on two CPUs (a producer that
// sees the cleared flag writes the fd, the consumer's read() swallows the
// write, the flag stands set over an empty fd and every later wake()
// returns early); in the replica it was the consensus thread never woken
// again. Two threads the scheduler keeps on ONE CPU never show it.
void test_wake_fd_keeps_every_wake() {
  pbft::WakeFd wake;
  CHECK(wake.open_fds());
  pbft::CmdQueue<int> q(1u << 22);
  constexpr int kItems = 200000;
  cpu_set_t allowed;
  CPU_ZERO(&allowed);
  std::vector<int> cpus;
  if (sched_getaffinity(0, sizeof(allowed), &allowed) == 0) {
    for (int c = 0; c < CPU_SETSIZE && cpus.size() < 2; ++c) {
      if (CPU_ISSET(c, &allowed)) cpus.push_back(c);
    }
  }
  auto pin = [&](size_t k) {
    if (cpus.size() < 2) return;
    cpu_set_t one;
    CPU_ZERO(&one);
    CPU_SET(cpus[k], &one);
    sched_setaffinity(0, sizeof(one), &one);
  };
  std::thread producer([&] {
    pin(0);
    for (int i = 0; i < kItems; ++i) {
      q.push(int(i), /*force=*/true);
      wake.wake();
      for (volatile int spin = 0; spin < 300; ++spin) {
      }
    }
  });
  pin(1);
  int taken = 0, lost = 0;
  std::deque<int> out;
  while (taken < kItems && lost == 0) {
    pollfd p{wake.fd(), POLLIN, 0};
    if (::poll(&p, 1, 1000) == 0 && q.size() > 0) ++lost;
    wake.drain();
    q.drain(&out);
    taken += (int)out.size();
    out.clear();
  }
  producer.join();
  if (cpus.size() == 2) sched_setaffinity(0, sizeof(allowed), &allowed);
  CHECK(lost == 0);
  CHECK(wake.wakes() >= 1 && wake.wakes() <= kItems);
  // The stamp of a drain's oldest entry: set by the push that found the
  // queue empty, handed back once, cleared by the drain.
  pbft::CmdQueue<int> stamped(8);
  using Stamp = pbft::CmdQueue<int>::Stamp;
  const auto before = std::chrono::steady_clock::now();
  stamped.push(1, false, /*stamp=*/true);
  stamped.push(2, false, /*stamp=*/true);
  Stamp oldest = Stamp::max();
  stamped.drain(&out, &oldest);
  CHECK(out.size() == 2 && oldest >= before &&
        oldest <= std::chrono::steady_clock::now());
  out.clear();
  stamped.push(3, false, /*stamp=*/false);
  oldest = Stamp::max();
  stamped.drain(&out, &oldest);
  CHECK(out.size() == 1 && oldest == Stamp::max());
}

void test_multicore_burst() {
  multicore_burst(1);
  multicore_burst(2);
  multicore_burst(4);
}

void test_multicore_parity() {
  const int64_t e1 = multicore_round(1);
  const int64_t e2 = multicore_round(2);
  const int64_t e4 = multicore_round(4);
  // Identical executed state across net-threads {1,2,4}: the shard tier
  // changes where the work runs, never what the cluster decides.
  CHECK(e1 == 2);
  CHECK(e2 == e1);
  CHECK(e4 == e1);
}

// --- ISSUE 37: the order of a pass on the async branch ----------------------
//
// A verifier the test scripts: begin_batch records the span it was given
// (and how many verdicts the replica had applied by then), the test makes
// the fd readable and says what poll_result returns.
struct ScriptedVerifier : pbft::Verifier {
  int fds[2] = {-1, -1};
  bool inflight = false;
  bool refuse = false;      // begin_batch: transport down
  bool fail_next = false;   // poll_result: the service died mid-launch
  pbft::Replica* replica = nullptr;
  std::vector<std::vector<pbft::VerifyItem>> launched, blocking;
  std::vector<int64_t> applied_at_launch, applied_at_blocking;
  int cancelled = 0;
  // Runs inside the pass that reads the verdicts, after its poller wait
  // and before the verdicts are worked through.
  std::function<void()> on_poll;

  ScriptedVerifier() { CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, fds) == 0); }
  ~ScriptedVerifier() override {
    ::close(fds[0]);
    ::close(fds[1]);
  }
  int64_t applied() {
    return replica->counters["sig_verified"] + replica->counters["sig_rejected"];
  }
  std::vector<uint8_t> verify_batch(
      const std::vector<pbft::VerifyItem>& items) override {
    blocking.push_back(items);
    applied_at_blocking.push_back(applied());
    return pbft::CpuVerifier().verify_batch(items);
  }
  int async_fd() const override { return inflight ? fds[0] : -1; }
  bool begin_batch(const std::vector<pbft::VerifyItem>& items) override {
    if (refuse || inflight) return false;
    launched.push_back(items);
    applied_at_launch.push_back(applied());
    inflight = true;
    return true;
  }
  bool poll_result(std::vector<uint8_t>* out, bool* failed) override {
    char b;
    CHECK(::read(fds[0], &b, 1) == 1);
    if (on_poll) on_poll();
    inflight = false;
    *failed = fail_next;
    if (!fail_next) *out = pbft::CpuVerifier().verify_batch(launched.back());
    return true;
  }
  void cancel_inflight() override {
    inflight = false;
    ++cancelled;
  }
  void answer() { CHECK(::write(fds[1], "v", 1) == 1); }
};

struct SpanLoop {
  std::vector<std::vector<uint8_t>> seeds;
  ScriptedVerifier* sv = nullptr;
  std::unique_ptr<pbft::ReplicaServer> srv;

  SpanLoop() {
    int ports[4];
    pbft::ClusterConfig cfg = loopback_config(1, ports, &seeds);
    auto v = std::make_unique<ScriptedVerifier>();
    sv = v.get();
    srv = std::make_unique<pbft::ReplicaServer>(cfg, 1, seeds[1].data(),
                                                std::move(v));
    sv->replica = &srv->replica();
    srv->metrics().enabled = true;
    CHECK(srv->start());
  }
  // A signed PREPARE from `from` straight into the verify inbox.
  void queue(int64_t seq, int64_t from, bool forged = false) {
    auto p = span_prepare(seq, from, seeds);
    if (forged) p.digest = std::string(64, 'b');
    srv->replica().receive(pbft::Message(p));
  }
  double sample(const std::string& name) {
    return prometheus_sample("\n" + srv->metrics().render_prometheus("1"), name);
  }
  // Through the server's own rendering, which first folds the counters
  // the loop keeps as plain integers (the loop clock's among them).
  double scraped(const std::string& name) {
    return prometheus_sample("\n" + srv->metrics_prometheus(), name);
  }
};

void test_loop_launches_ahead_of_kept_verdicts() {
  SpanLoop t;
  t.queue(1, 2);
  t.queue(1, 3);
  t.srv->poll_once(0);
  CHECK(t.sv->launched.size() == 1 && t.sv->launched[0].size() == 2);
  // Span 2 accumulates during the trip; nothing more is launched.
  t.queue(2, 2);
  t.queue(2, 3, /*forged=*/true);
  t.queue(2, 0);
  t.srv->poll_once(0);
  CHECK(t.sv->launched.size() == 1);
  CHECK(t.sample("pbft_verify_launched_ahead_total") == 0);
  // The verdicts come back: the SAME pass ships span 2 first (none of span
  // 1's verdicts applied at that moment), then works through span 1.
  t.sv->answer();
  t.srv->poll_once(50);
  CHECK(t.sv->launched.size() == 2 && t.sv->launched[1].size() == 3);
  CHECK(t.sv->applied_at_launch[1] == 0);
  CHECK(t.srv->replica().counters["prepares_accepted"] == 2);
  CHECK(t.srv->replica().pending_count() == 3);
  CHECK(t.sample("pbft_verify_launched_ahead_total") == 1);
  CHECK(t.sample("pbft_verify_batches_total") == 1);
  CHECK(t.sample("pbft_verdict_held_seconds_count") == 1);
  CHECK(t.sample("pbft_verdict_apply_seconds_count") == 1);
  // Span 2 comes back with nothing behind it: no launch, its forgery is
  // dropped, the clock on held verdicts still runs once a batch.
  t.sv->answer();
  t.srv->poll_once(50);
  CHECK(t.sv->launched.size() == 2 && t.sv->blocking.empty());
  CHECK(t.srv->replica().counters["prepares_accepted"] == 4);
  CHECK(t.srv->replica().counters["sig_rejected"] == 1);
  CHECK(t.srv->replica().pending_count() == 0);
  CHECK(t.sample("pbft_verify_launched_ahead_total") == 1);
  CHECK(t.sample("pbft_verify_batches_total") == 2);
  CHECK(t.sample("pbft_verdict_held_seconds_count") == 2);
  // The span that closes the cycle: once a kept batch, and no shorter
  // than nothing (delivery began -> deliver_verified returned).
  CHECK(t.sample("pbft_verdict_apply_seconds_count") == 2);
  CHECK(t.sample("pbft_verdict_apply_seconds_sum") > 0);
  CHECK(t.sample("pbft_verify_seconds_count") == 2);
  CHECK(t.sample("pbft_verify_service_fallbacks_total") == 0);
}

void test_loop_wedge_deadline_keeps_order() {
  // The wedged trip: the clock is the wire's, the safety net runs on the
  // span that is on the wire and on nothing behind it, and its verdicts
  // are kept like any others — the span behind goes out first.
  SpanLoop t;
  t.srv->set_verify_deadline_ms(40);
  t.queue(1, 2);
  t.srv->poll_once(0);
  t.queue(1, 3);
  t.queue(2, 2, /*forged=*/true);
  t.sv->answer();
  t.srv->poll_once(50);  // span 2 launched ahead, span 1 applied
  CHECK(t.sv->launched.size() == 2 && t.sv->applied_at_launch[1] == 0);
  CHECK(t.srv->replica().counters["prepares_accepted"] == 1);
  t.queue(2, 3);  // span 3, behind the wedged trip
  std::this_thread::sleep_for(std::chrono::milliseconds(60));
  t.srv->poll_once(0);  // no answer ever comes: the deadline fires
  CHECK(t.sv->cancelled == 1);
  CHECK(t.sample("pbft_verify_deadline_fired_total") == 1);
  CHECK(t.sample("pbft_verify_service_fallbacks_total") == 1);
  // Span 3 went out before the safety net's verdicts for span 2 were
  // applied (one applied so far: span 1's), holding span 3's entry only.
  CHECK(t.sv->launched.size() == 3 && t.sv->launched[2].size() == 1);
  CHECK(t.sv->applied_at_launch[2] == 1);
  CHECK(t.sv->blocking.empty());
  CHECK(t.srv->replica().counters["prepares_accepted"] == 2);
  CHECK(t.srv->replica().counters["sig_rejected"] == 1);
  CHECK(t.srv->replica().pending_count() == 1);
  CHECK(t.sample("pbft_verify_launched_ahead_total") == 2);
  t.sv->answer();
  t.srv->poll_once(50);
  CHECK(t.srv->replica().counters["prepares_accepted"] == 3);
  CHECK(t.srv->replica().pending_count() == 0);
}

void test_loop_transport_failure_keeps_order() {
  // The service dies mid-launch and stays down: the safety net verifies
  // the wire's span only; the span behind it cannot be shipped, so the
  // kept verdicts are applied BEFORE its blocking verify, never after.
  SpanLoop t;
  t.queue(1, 2);
  t.queue(1, 3, /*forged=*/true);
  t.srv->poll_once(0);
  CHECK(t.sv->launched.size() == 1);
  t.queue(2, 2);
  t.queue(2, 3);
  t.sv->fail_next = true;
  t.sv->refuse = true;
  t.sv->answer();
  t.srv->poll_once(50);
  CHECK(t.sv->launched.size() == 1);
  CHECK(t.sv->blocking.size() == 1 && t.sv->blocking[0].size() == 2);
  CHECK(t.sv->applied_at_blocking[0] == 2);  // span 1 whole, forgery included
  CHECK(t.srv->replica().counters["sig_rejected"] == 1);
  CHECK(t.srv->replica().counters["prepares_accepted"] == 3);
  CHECK(t.srv->replica().pending_count() == 0);
  CHECK(t.sample("pbft_verify_service_fallbacks_total") == 1);
  CHECK(t.sample("pbft_verify_launched_ahead_total") == 0);
  CHECK(t.sample("pbft_verify_batches_total") == 2);
  CHECK(t.sample("pbft_verdict_held_seconds_count") == 1);  // async branch only
  CHECK(t.sample("pbft_verdict_apply_seconds_count") == 1);  // kept spans only
  CHECK(t.sample("pbft_verify_inbox_wait_seconds_count") == 0);  // no emit() stamped one
}

// --- ISSUE 38: the loop's stage clock ---------------------------------------
void spin_for(std::chrono::microseconds d) {
  const auto until = std::chrono::steady_clock::now() + d;
  while (std::chrono::steady_clock::now() < until) {
  }
}

void test_loop_clock_unit() {
  using pbft::LoopClock;
  // Off: a Scope is one branch; no stage, total or switch count moves.
  LoopClock off;
  {
    LoopClock::Scope a(off, pbft::kLoopRead);
    LoopClock::Scope b(off, pbft::kLoopProtocol);
    spin_for(std::chrono::microseconds(200));
  }
  CHECK(off.total_ns() == 0 && off.switches == 0);
  CHECK(off.stage == pbft::kLoopOther);

  // On: nested scopes charge EXCLUSIVE time, and the seven sum to the
  // elapsed time between the clock coming on and its last sync.
  LoopClock c;
  const auto t0 = std::chrono::steady_clock::now();
  c.set_on(true);
  spin_for(std::chrono::microseconds(300));  // other
  {
    LoopClock::Scope read(c, pbft::kLoopRead);
    spin_for(std::chrono::microseconds(500));
    {
      LoopClock::Scope proto(c, pbft::kLoopProtocol);
      spin_for(std::chrono::microseconds(2000));
      {
        LoopClock::Scope wal(c, pbft::kLoopWal);
        spin_for(std::chrono::microseconds(700));
      }
      {
        LoopClock::Scope send(c, pbft::kLoopSend);
        spin_for(std::chrono::microseconds(400));
        LoopClock::Scope same(c, pbft::kLoopSend);  // no switch, no read
        spin_for(std::chrono::microseconds(100));
      }
    }
    spin_for(std::chrono::microseconds(500));
  }
  c.sync();
  const auto t1 = std::chrono::steady_clock::now();
  const int64_t elapsed =
      std::chrono::duration_cast<std::chrono::nanoseconds>(t1 - t0).count();
  CHECK(c.stage == pbft::kLoopOther);
  CHECK(c.switches == 8);  // four scopes that switched, in and out
  CHECK(c.ns[pbft::kLoopWait] == 0 && c.ns[pbft::kLoopVerify] == 0);
  CHECK(c.ns[pbft::kLoopOther] >= 300 * 1000);
  CHECK(c.ns[pbft::kLoopRead] >= 1000 * 1000);
  CHECK(c.ns[pbft::kLoopRead] < 1400 * 1000);  // the 3.2 ms nested are not its
  CHECK(c.ns[pbft::kLoopProtocol] >= 2000 * 1000);
  CHECK(c.ns[pbft::kLoopProtocol] < 2400 * 1000);
  CHECK(c.ns[pbft::kLoopWal] >= 700 * 1000);
  CHECK(c.ns[pbft::kLoopSend] >= 500 * 1000);
  CHECK(c.total_ns() <= elapsed);
  CHECK(c.total_ns() > elapsed - 200 * 1000);  // set_on .. sync, to 0.2 ms

  // What a switch costs (PERF.md carries the figure): one clock read and
  // three integer operations; in and out of a scope is two.
  LoopClock bench;
  bench.set_on(true);
  const int kPairs = 200000;
  const auto b0 = std::chrono::steady_clock::now();
  for (int i = 0; i < kPairs; ++i) {
    LoopClock::Scope in(bench, pbft::kLoopSend);
  }
  const double ns_a_switch =
      std::chrono::duration<double, std::nano>(
          std::chrono::steady_clock::now() - b0)
          .count() /
      (2.0 * kPairs);
  CHECK(bench.switches == 2 * kPairs);
  std::printf("loop clock: %.1f ns a stage switch\n", ns_a_switch);
  CHECK(ns_a_switch < 2000);  // a vDSO clock read, not a system call gone wrong
}

void test_loop_clock_on_the_loop() {
  // Metrics and trace both off: passes run, nothing is timed.
  {
    SpanLoop t;
    t.srv->metrics().enabled = false;
    t.queue(1, 2);
    t.srv->poll_once(0);
    t.sv->answer();
    t.srv->poll_once(50);
    CHECK(t.srv->replica().counters["prepares_accepted"] == 1);
    CHECK(t.srv->loop_clock().total_ns() == 0);
    CHECK(t.srv->loop_clock().switches == 0);
  }
  // On: the scrape folds the stages, they sum to the total to the
  // microsecond and to the wall time the passes took, `wait` holds the
  // poller's timeout and `verify` / `protocol` the work of the batches.
  SpanLoop t;
  const auto t0 = std::chrono::steady_clock::now();
  t.queue(1, 2);
  t.queue(1, 3);
  t.srv->poll_once(0);
  t.sv->answer();
  t.srv->poll_once(50);
  t.srv->poll_once(30);  // nothing to do: 30 ms inside wait
  const double total = t.scraped("pbft_loop_us_total");
  const double wall_us = std::chrono::duration<double, std::micro>(
                             std::chrono::steady_clock::now() - t0)
                             .count();
  CHECK(total > 30000 && total <= wall_us);
  CHECK(t.scraped("pbft_loop_wait_us_total") >= 29000);
  CHECK(t.scraped("pbft_loop_verify_us_total") > 0);
  CHECK(t.scraped("pbft_loop_protocol_us_total") > 0);
  CHECK(t.scraped("pbft_epoll_wakeups_total") == 3);
  CHECK(t.scraped("pbft_verdict_apply_seconds_count") == 1);
  // One rendering is one instant: there the eight agree to the microsecond.
  const std::string text = "\n" + t.srv->metrics_prometheus();
  double one_sum = 0;
  for (const char* stage : pbft::kLoopStageNames) {
    one_sum += prometheus_sample(
        text, std::string("pbft_loop_") + stage + "_us_total");
  }
  CHECK(one_sum == prometheus_sample(text, "pbft_loop_us_total"));
  // /status carries the same seven, the passes and the applied batches.
  const std::string status = t.srv->metrics_json();
  CHECK(status.find("\"loop_us\":{") != std::string::npos);
  CHECK(status.find("\"passes\":3") != std::string::npos);
  CHECK(status.find("\"verify_apply\":{\"batches\":1") != std::string::npos);
}

// --- ISSUE 41: one flush a connection an emit --------------------------------
//
// The witness of what the send side did, and in which order, is outside the
// program: core_test defines send() and fsync() itself. An executable's
// symbols come before libc's, so the dynamic linker binds libpbftcore's
// calls to these two; each passes its call on to the kernel and, while a
// test has set g_witness, tells it. No hook in ReplicaServer or NetShard.
struct SyscallWitness {
  virtual ~SyscallWitness() = default;
  virtual void sent(int fd, const void* buf, size_t n, ssize_t ret) = 0;
  virtual void synced(int fd) = 0;
};
std::atomic<SyscallWitness*> g_witness{nullptr};

extern "C" ssize_t send(int fd, const void* buf, size_t n, int flags) {
  const ssize_t ret = ::sendto(fd, buf, n, flags, nullptr, 0);
  if (SyscallWitness* w = g_witness.load(std::memory_order_acquire)) {
    const int err = errno;
    w->sent(fd, buf, n, ret);
    errno = err;
  }
  return ret;
}

extern "C" int fsync(int fd) {
  const int ret = (int)::syscall(SYS_fsync, fd);
  if (SyscallWitness* w = g_witness.load(std::memory_order_acquire)) {
    const int err = errno;
    w->synced(fd);
    errno = err;
  }
  return ret;
}

// The payloads of a stream of length-prefixed frames; *rest: what is left
// behind the last whole frame.
std::vector<std::string> split_frames(const std::string& stream, size_t* rest) {
  std::vector<std::string> out;
  size_t at = 0;
  while (stream.size() - at >= 4) {
    const uint32_t len = ((uint32_t)(uint8_t)stream[at] << 24) |
                         ((uint32_t)(uint8_t)stream[at + 1] << 16) |
                         ((uint32_t)(uint8_t)stream[at + 2] << 8) |
                         (uint32_t)(uint8_t)stream[at + 3];
    if (stream.size() - at - 4 < len) break;
    out.push_back(stream.substr(at + 4, len));
    at += 4 + (size_t)len;
  }
  *rest = stream.size() - at;
  return out;
}

// Replica 1 (a backup in view 0) as a real ReplicaServer behind the scripted
// verifier; the test is the rest of the world: its three peers (listening
// sockets on their configured ports, which the server dials), its one
// gateway (a link with a role=gateway hello, which every "gw/" reply fans
// back over) and the witness of its system calls. The rounds it is fed are
// real ones: a shadow cluster of plain Replica objects runs each batch to
// completion; what that addressed to replica 1 is queued into the server's
// verify inbox, and what ITS replica 1 sent (the same seed signs the same
// bytes) is what the far ends have to receive, byte for byte. ONE
// deliver_verdicts then works through a pre-prepare, its prepares and its
// commits: two broadcasts (PREPARE, COMMIT) and a reply a request, out of
// one emit().
struct SendRig : SyscallWitness {
  static constexpr int kMe = 1;
  static constexpr int kGateway = -1;  // the gateway's key beside 0, 2, 3
  struct Ev {
    bool sync;          // an fsync(); else a send()
    int fd;
    std::string bytes;  // send(): the bytes the kernel took
    ssize_t ret;        // send(): what it returned
  };
  // What a stretch of the log did to one of the server's connections.
  struct Use {
    int sends = 0, failed = 0;
    int first_send = -1, last_send = -1;
    std::string bytes;  // taken by the kernel, in order
    size_t frames = 0;  // whole frames in them
  };

  std::vector<std::vector<uint8_t>> seeds;
  pbft::ClusterConfig cfg;
  int ports[4];
  int listeners[4] = {-1, -1, -1, -1};
  int peers[4] = {-1, -1, -1, -1};  // the accepted ends of the server's dials
  int gateway = -1;
  ScriptedVerifier* sv = nullptr;
  std::unique_ptr<pbft::ReplicaServer> srv;
  std::unique_ptr<MiniCluster> shadow;
  std::string wal_dir, wal_path;
  std::vector<Ev> log;
  std::map<int, int> far_port;     // the server's fd -> its far end's port
  std::map<int, std::string> got;  // the test's fd -> every byte read
  // What the shadow's replica 1 sent each far end (a peer's id, kGateway),
  // framed, and how many bytes the link carried before the first of it
  // (a peer's: the server's hello).
  std::map<int, std::string> want;
  std::map<int, size_t> skip;
  int64_t rounds = 0;
  int sends_outside_the_send_stage = 0;  // by the loop's stage clock
  // With a WAL: the log file's size at its last fsync, and the votes that
  // were looked up in the file at the instant send() was handed them.
  int64_t synced_size = -1;
  int votes_found_on_disk = 0;

  explicit SendRig(bool wal = false) {
    cfg = loopback_config(57, ports, &seeds);
    cfg.batch_max_items = 1024;
    auto v = std::make_unique<ScriptedVerifier>();
    sv = v.get();
    srv = std::make_unique<pbft::ReplicaServer>(cfg, kMe, seeds[kMe].data(),
                                                std::move(v));
    sv->replica = &srv->replica();
    srv->metrics().enabled = true;
    if (wal) {
      const char* tmp = std::getenv("TMPDIR");
      wal_dir = std::string(tmp ? tmp : "/tmp") + "/pbft-send-wal-XXXXXX";
      CHECK(::mkdtemp(wal_dir.data()) != nullptr);
      wal_path = wal_dir + "/replica-1.wal";
      CHECK(srv->enable_wal(wal_dir));
    }
    CHECK(srv->start());
    for (int k : {0, 2, 3}) {
      listeners[k] = listen_on_port(ports[k]);
      CHECK(listeners[k] >= 0);
    }
    shadow = std::make_unique<MiniCluster>(cfg, seeds);
    gateway = pbft::dial_tcp("127.0.0.1:" + std::to_string(ports[kMe]));
    CHECK(gateway >= 0);
    const std::string hello = gateway_hello_frame();
    CHECK(::write(gateway, hello.data(), hello.size()) == (ssize_t)hello.size());
    g_witness.store(this, std::memory_order_release);
    srv->poll_once(50);
    srv->poll_once(0);
  }
  ~SendRig() override {
    g_witness.store(nullptr, std::memory_order_release);
    for (int fd : listeners) {
      if (fd >= 0) ::close(fd);
    }
    for (int fd : peers) {
      if (fd >= 0) ::close(fd);
    }
    if (gateway >= 0) ::close(gateway);
    srv.reset();
    if (!wal_dir.empty()) {
      ::unlink(wal_path.c_str());
      ::rmdir(wal_dir.c_str());
    }
  }

  int stub(int k) const { return k == kGateway ? gateway : peers[k]; }

  void sent(int fd, const void* buf, size_t n, ssize_t ret) override {
    for (int k : {kGateway, 0, 2, 3}) {
      if (fd == stub(k)) return;  // the test's own end of a link
    }
    if (!far_port.count(fd)) {
      const int port = socket_port(fd, /*far_end=*/true);
      if (port > 0) far_port[fd] = port;
    }
    if (srv->loop_clock().stage != pbft::kLoopSend) {
      ++sends_outside_the_send_stage;
    }
    if (!wal_path.empty()) votes_are_on_disk(std::string((const char*)buf, n));
    log.push_back(Ev{false, fd,
                     ret > 0 ? std::string((const char*)buf, (size_t)ret) : "",
                     ret});
  }
  void synced(int fd) override {
    struct stat st{};
    if (::fstat(fd, &st) == 0 && S_ISREG(st.st_mode)) synced_size = st.st_size;
    log.push_back(Ev{true, fd, "", 0});
  }
  // The guarantee, at the instant a vote is handed to the kernel: its
  // record is in the log file, and nothing was written there since the
  // last fsync.
  void votes_are_on_disk(const std::string& handed) {
    std::string image;
    if (FILE* f = std::fopen(wal_path.c_str(), "rb")) {
      char buf[65536];
      for (size_t r; (r = std::fread(buf, 1, sizeof(buf), f)) > 0;) {
        image.append(buf, r);
      }
      std::fclose(f);
    }
    pbft::WalState on_disk;
    const bool readable = pbft::wal_decode(image, &on_disk);
    size_t rest = 0;
    for (const std::string& payload : split_frames(handed, &rest)) {
      auto m = pbft::from_payload(payload);
      if (!m) continue;  // the link's hello
      uint8_t kind = 0;
      int64_t view = 0, seq = 0;
      std::string digest;
      if (auto* p = std::get_if<pbft::Prepare>(&*m)) {
        kind = pbft::kWalVotePrepare, view = p->view, seq = p->seq, digest = p->digest;
      } else if (auto* c = std::get_if<pbft::Commit>(&*m)) {
        kind = pbft::kWalVoteCommit, view = c->view, seq = c->seq, digest = c->digest;
      } else {
        continue;
      }
      CHECK(readable && (int64_t)image.size() == synced_size);
      auto it = on_disk.votes.find({kind, view, seq});
      CHECK(it != on_disk.votes.end() && it->second == digest);
      if (it != on_disk.votes.end() && it->second == digest) ++votes_found_on_disk;
    }
  }

  // A batch of n requests ("gw/" clients, one request a client) run to its
  // end by the shadow cluster: what replicas 0, 2 and 3 sent replica 1.
  // What replica 1 sent there is added to what the far ends must receive.
  std::vector<pbft::Message> script(int n) {
    std::vector<pbft::Message> to_me;
    shadow->tap = [&](int src, int dst, const pbft::Message& m) {
      if (dst == kMe) to_me.push_back(m);
      if (src == kMe) want[dst] += pbft::frame_payload(pbft::message_canonical(m));
    };
    const size_t replies_before = shadow->replies.size();
    ++rounds;
    for (int i = 0; i < n; ++i) {
      pbft::ClientRequest req;
      req.operation = "op-" + std::to_string(rounds) + "-" + std::to_string(i);
      req.timestamp = rounds;
      req.client = "gw/c" + std::to_string(i);
      shadow->emit(0, shadow->replicas[0].on_client_request(req));
    }
    shadow->emit(0, shadow->replicas[0].flush_open_batch());
    shadow->run();
    shadow->tap = nullptr;
    for (size_t i = replies_before; i < shadow->replies.size(); ++i) {
      const pbft::ClientReply& r = shadow->replies[i];
      if (r.replica == kMe) want[kGateway] += pbft::frame_payload(r.to_json().dump());
    }
    return to_me;
  }
  void feed(const std::vector<pbft::Message>& msgs) {
    for (const auto& m : msgs) srv->replica().receive(m);
  }
  // The inbox goes out as one span; its verdicts come back and are worked
  // through by one deliver_verdicts (one emit).
  void cycle() {
    srv->poll_once(0);
    sv->answer();
    srv->poll_once(50);
  }
  void accept_peers() {
    for (int k : {0, 2, 3}) {
      if (peers[k] >= 0) continue;
      pollfd p{listeners[k], POLLIN, 0};
      for (int tries = 0; tries < 40 && ::poll(&p, 1, 0) == 0; ++tries) {
        srv->poll_once(50);
      }
      peers[k] = ::accept(listeners[k], nullptr, nullptr);
      CHECK(peers[k] >= 0);
    }
  }
  void pump() {
    for (int k : {kGateway, 0, 2, 3}) {
      if (stub(k) < 0) continue;
      char buf[65536];
      for (;;) {
        const ssize_t r = ::recv(stub(k), buf, sizeof(buf), MSG_DONTWAIT);
        if (r <= 0) break;
        got[stub(k)].append(buf, (size_t)r);
      }
    }
  }
  // A link that was just made: passes and reads until its far end holds
  // all it is owed behind what the link itself opened with (at most 5 s).
  bool learn_skip(int k) {
    const auto deadline =
        std::chrono::steady_clock::now() + std::chrono::seconds(5);
    for (;;) {
      pump();
      const std::string& g = got[stub(k)];
      const std::string& w = want[k];
      const size_t opened_with = k == kGateway ? 0 : 1;
      if (g.size() >= w.size() + opened_with &&
          g.compare(g.size() - w.size(), w.size(), w) == 0) {
        skip[k] = g.size() - w.size();
        size_t rest = 1;
        const auto opening = split_frames(g.substr(0, skip[k]), &rest);
        return rest == 0 && opening.size() >= opened_with;
      }
      if (std::chrono::steady_clock::now() > deadline) return false;
      srv->poll_once(10);
    }
  }
  // A first round of one request: the server dials its three peers, the
  // test accepts, every link has carried what it opens with and a frame.
  void warm_up() {
    feed(script(1));
    cycle();
    accept_peers();
    for (int k : {kGateway, 0, 2, 3}) CHECK(learn_skip(k));
    CHECK(srv->replica().counters["executed"] == 1);
  }
  // Every far end holds, byte for byte and in order, what replica 1 was to
  // send it: read now, with no further pass of the loop. True after an
  // emit means the emit left nothing behind for a later one to flush.
  bool arrived() {
    pump();
    for (const auto& [k, bytes] : want) {
      if (stub(k) < 0) return false;
      const std::string& g = got[stub(k)];
      if (g.size() != skip[k] + bytes.size() ||
          g.compare(skip[k], bytes.size(), bytes) != 0) {
        return false;
      }
    }
    return true;
  }
  // The same after passes that are given nothing to emit (at most 5 s):
  // what a full socket left queued goes out on write readiness alone.
  bool settle_streams() {
    const auto deadline =
        std::chrono::steady_clock::now() + std::chrono::seconds(5);
    while (!arrived()) {
      if (std::chrono::steady_clock::now() > deadline) return false;
      srv->poll_once(10);
    }
    return true;
  }
  int server_fd_of(int stub_fd) {
    for (const auto& [fd, port] : far_port) {
      for (int k : {0, 2, 3}) {
        if (port == ports[k] && peers[k] == stub_fd) return fd;
      }
      if (stub_fd == gateway && port == socket_port(gateway, false)) return fd;
    }
    return -1;
  }
  std::map<int, Use> uses(size_t from) const {
    std::map<int, Use> out;
    for (size_t i = from; i < log.size(); ++i) {
      const Ev& e = log[i];
      if (e.sync) continue;
      Use& u = out[e.fd];
      ++u.sends;
      if (e.ret < 0) ++u.failed;
      u.bytes += e.bytes;
      if (u.first_send < 0) u.first_send = (int)i;
      u.last_send = (int)i;
    }
    for (auto& [_, u] : out) {
      size_t rest = 0;
      u.frames = split_frames(u.bytes, &rest).size();
    }
    return out;
  }
  double scraped(const std::string& name) {
    return prometheus_sample("\n" + srv->metrics_prometheus(), name);
  }
};

// (a) N replies to one gateway link and the votes of two sequence numbers
// to three peers, out of ONE deliver_verdicts: a send() a connection.
void test_emit_sends_once_a_connection() {
  SendRig t;
  t.warm_up();
  const size_t mark = t.log.size();
  const double frames0 = t.scraped("pbft_frames_out_total");
  const double sends0 = t.scraped("pbft_send_calls_total");
  CHECK(frames0 > 0 && sends0 > 0);
  const auto first = t.script(16), second = t.script(16);
  t.feed(first);
  t.feed(second);
  t.cycle();
  CHECK(t.srv->replica().counters["executed"] == 33);
  auto use = t.uses(mark);
  CHECK(use.size() == 4);
  const int gw = t.server_fd_of(t.gateway);
  CHECK(use[gw].frames == 32 && use[gw].sends == 1 && use[gw].failed == 0);
  for (int k : {0, 2, 3}) {
    const SendRig::Use& u = use[t.server_fd_of(t.peers[k])];
    // PREPARE and COMMIT of each of the two sequence numbers, one send().
    CHECK(u.frames == 4 && u.sends == 1 && u.failed == 0);
    // The votes leave before the replies do.
    CHECK(u.last_send < use[gw].first_send);
  }
  CHECK(t.scraped("pbft_frames_out_total") == frames0 + 32 + 12);
  CHECK(t.scraped("pbft_send_calls_total") == sends0 + 4);
  CHECK(t.arrived());
  // The flush that ends an emit is the `send` stage's, like the queueing.
  CHECK(t.srv->loop_clock().on && t.sends_outside_the_send_stage == 0);
}

// (b) What a peer and the gateway receive over a scripted run is what
// replica 1 had to send them, byte for byte and in order; past 64 KiB a
// connection takes a send() a block.
void test_flushed_streams_are_the_queued_frames() {
  SendRig t;
  t.warm_up();
  for (int n : {1, 32, 5}) {
    t.feed(t.script(n));
    t.cycle();
    CHECK(t.arrived());
  }
  const size_t mark = t.log.size();
  t.feed(t.script(300));  // ~100 KB of replies
  t.feed(t.script(2));
  t.cycle();
  auto use = t.uses(mark);
  const SendRig::Use& g = use[t.server_fd_of(t.gateway)];
  const int blocks = (int)(g.bytes.size() / pbft::max_send_block()) + 1;
  CHECK(g.frames == 302 && blocks >= 2);
  CHECK(g.sends >= blocks && g.sends <= 3 * blocks && g.failed == 0);
  for (int k : {0, 2, 3}) {
    CHECK(use[t.server_fd_of(t.peers[k])].sends == 1);
  }
  CHECK(t.arrived());
  CHECK(t.srv->replica().counters["executed"] == 1 + 38 + 302);
  // What was compared: a reply a request on the gateway's link, and on a
  // peer's PREPARE and COMMIT of every sequence number.
  size_t rest = 1;
  CHECK(split_frames(t.want[SendRig::kGateway], &rest).size() == 1 + 38 + 302);
  CHECK(rest == 0);
  for (int k : {0, 2, 3}) {
    CHECK(split_frames(t.want[k], &rest).size() == 2 * 6 && rest == 0);
    CHECK(t.want[k] == t.want[0] && t.skip[k] > 0);
  }
}

// (c) One frame in an emit is one send(), as before.
void test_one_frame_in_an_emit_is_one_send() {
  SendRig t;
  t.warm_up();
  const auto round = t.script(4);
  CHECK(std::holds_alternative<pbft::PrePrepare>(round[0]));
  const size_t mark = t.log.size();
  const double frames0 = t.scraped("pbft_frames_out_total");
  const double sends0 = t.scraped("pbft_send_calls_total");
  t.feed({round[0]});  // the pre-prepare alone: one PREPARE to each peer
  t.cycle();
  auto use = t.uses(mark);
  CHECK(use.size() == 3);
  for (const auto& [fd, u] : use) {
    CHECK(fd != t.server_fd_of(t.gateway));
    CHECK(u.frames == 1 && u.sends == 1 && u.failed == 0);
  }
  CHECK(t.scraped("pbft_frames_out_total") == frames0 + 3);
  CHECK(t.scraped("pbft_send_calls_total") == sends0 + 3);
  // The rest of the round: one COMMIT a peer and four replies.
  const size_t mark2 = t.log.size();
  t.feed({round.begin() + 1, round.end()});
  t.cycle();
  use = t.uses(mark2);
  CHECK(use.size() == 4);
  for (const auto& [fd, u] : use) {
    CHECK(u.frames == (fd == t.server_fd_of(t.gateway) ? 4u : 1u));
    CHECK(u.sends == 1);
  }
  CHECK(t.scraped("pbft_send_calls_total") == sends0 + 3 + 4);
  CHECK(t.arrived());
}

// (d) When an emit returns, and so when a pass does, no connection is left
// for a later one to flush: what it queued is at the far end, or waits on
// write readiness that is armed and goes out on that alone. Seen from the
// far ends, also while a gateway that does not read backs its link up.
void test_no_byte_waits_for_a_later_emit() {
  SendRig t;
  t.warm_up();
  for (int n : {3, 1, 20}) {
    t.feed(t.script(n));
    t.cycle();
    CHECK(t.arrived());  // no pass since the emit's
  }
  const int gw = t.server_fd_of(t.gateway);
  int small = 4096;
  CHECK(::setsockopt(gw, SOL_SOCKET, SO_SNDBUF, &small, sizeof(small)) == 0);
  CHECK(::setsockopt(t.gateway, SOL_SOCKET, SO_RCVBUF, &small,
                     sizeof(small)) == 0);
  const double backed_up0 = t.scraped("pbft_write_backpressure_events_total");
  const double frames0 = t.scraped("pbft_frames_out_total");
  const size_t mark = t.log.size();
  const auto big = t.script(900);  // ~300 KB of replies
  t.feed(big);
  // Nobody reads the gateway's end while the emit runs.
  t.srv->poll_once(0);
  t.sv->answer();
  t.srv->poll_once(50);
  auto use = t.uses(mark);
  CHECK(use[gw].failed == 1);  // EAGAIN: the flush stops there
  CHECK(t.log[(size_t)use[gw].last_send].ret < 0);
  size_t rest = 0;
  CHECK(use[gw].frames < 900);
  // Write readiness was armed once for the episode; what the next emit
  // queues behind it waits for the same edge.
  CHECK(t.scraped("pbft_write_backpressure_events_total") == backed_up0 + 1);
  t.feed(t.script(8));
  t.srv->poll_once(0);
  t.sv->answer();
  t.srv->poll_once(50);
  CHECK(t.scraped("pbft_write_backpressure_events_total") == backed_up0 + 1);
  CHECK(t.scraped("pbft_frames_out_total") == frames0 + 908 + 2 * 2 * 3);
  CHECK(t.srv->replica().counters["executed"] == 1 + 24 + 908);
  CHECK(!t.arrived());
  // The gateway reads: the flush resumes on the edge, with no emit to help
  // it, and every byte arrives, in order.
  CHECK(t.settle_streams());
  CHECK(split_frames(t.want[SendRig::kGateway], &rest).size() == 1 + 24 + 908);
}

// (e) The WAL is flushed before the first byte of an emit leaves: when a
// vote is handed to send(), its record is in the file and fsynced.
void test_wal_flush_precedes_an_emits_first_byte() {
  SendRig t(/*wal=*/true);
  t.warm_up();
  const int found0 = t.votes_found_on_disk;
  CHECK(found0 == 2 * 3);  // the first round's PREPARE and COMMIT, a peer
  const double fsyncs0 = t.scraped("pbft_wal_fsyncs_total");
  const size_t mark = t.log.size();
  t.feed(t.script(16));
  t.feed(t.script(16));
  t.cycle();
  int synced_at = -1, sent_at = -1, syncs = 0;
  for (size_t i = mark; i < t.log.size(); ++i) {
    if (t.log[i].sync) ++syncs;
    if (t.log[i].sync && synced_at < 0) synced_at = (int)i;
    if (!t.log[i].sync && sent_at < 0) sent_at = (int)i;
  }
  // The votes this emit carries were noted by the deliver_verdicts that
  // produced it: one group commit, then the first send().
  CHECK(syncs == 1 && synced_at >= 0 && sent_at > synced_at);
  CHECK(t.scraped("pbft_wal_fsyncs_total") == fsyncs0 + 1);
  // Each of the 12 votes was looked up in the file as send() was handed it
  // (SendRig::votes_are_on_disk holds every one to it).
  CHECK(t.votes_found_on_disk == found0 + 2 * 2 * 3);
  CHECK(t.arrived());
}

// (f) A connection that a failed send() closes in the middle of an emit's
// flush is passed over from there on; the emit serves the others.
void test_connection_closed_by_failed_send_is_passed_over() {
  SendRig t;
  t.warm_up();
  const int victim = t.server_fd_of(t.peers[2]);
  CHECK(victim >= 0);
  // Peer 2 resets its link after the pass's poller wait and before the
  // verdicts are worked through: the loop learns of it from send().
  t.sv->on_poll = [&t, victim] {
    linger lg{1, 0};
    CHECK(::setsockopt(t.peers[2], SOL_SOCKET, SO_LINGER, &lg, sizeof(lg)) == 0);
    ::close(t.peers[2]);
    t.got.erase(t.peers[2]);  // the number may come back as the next link's
    t.peers[2] = -1;
    pollfd p{victim, 0, 0};  // POLLERR / POLLHUP need not be asked for
    CHECK(::poll(&p, 1, 2000) == 1);
  };
  const size_t mark = t.log.size();
  t.feed(t.script(8));
  t.cycle();
  t.sv->on_poll = nullptr;
  auto use = t.uses(mark);
  // PREPARE and COMMIT were queued on it: one send(), which failed, and
  // none after it to the end of the pass.
  CHECK(use[victim].sends == 1 && use[victim].failed == 1);
  const int gw = t.server_fd_of(t.gateway);
  CHECK(use[gw].frames == 8 && use[gw].sends == 1 && use[gw].failed == 0);
  CHECK(use[gw].first_send > use[victim].last_send);
  for (int k : {0, 3}) {
    const SendRig::Use& u = use[t.server_fd_of(t.peers[k])];
    CHECK(u.frames == 2 && u.sends == 1 && u.failed == 0);
  }
  // That link is gone, with what it was owed; the others hold theirs.
  t.far_port.erase(victim);
  t.want.erase(2);
  CHECK(t.arrived());
  // The next round dials peer 2 again and reaches all three.
  t.feed(t.script(1));
  t.cycle();
  t.accept_peers();
  CHECK(t.peers[2] >= 0 && t.learn_skip(2));
  CHECK(t.settle_streams());
  CHECK(!t.got[t.peers[2]].empty());
}

// (g) The same stage in a loop shard: K writes to one connection in one
// drained stretch are one send(), and a close that follows writes in the
// same stretch delivers them first. The shard's thread body runs on a
// thread of the test's, a stretch at a time: what was pushed before it
// started is the ONE drain of its first pass.
void test_shard_sends_once_a_drained_stretch() {
  int ports[4];
  std::vector<std::vector<uint8_t>> seeds;
  pbft::ClusterConfig cfg = loopback_config(63, ports, &seeds);
  std::atomic<bool> stopping{false};
  pbft::NetShards shards(cfg, 1, seeds[1].data(), &stopping, 1);
  pbft::NetShard& shard = shards.shard(0);
  shards.set_clocks_on(true);
  int bound = 0;
  CHECK(shard.bind_listener(ports[1], /*reuseport=*/false, &bound));
  const int link = pbft::dial_tcp("127.0.0.1:" + std::to_string(bound));
  CHECK(link >= 0);
  auto run_until = [&](const std::function<bool()>& done) {
    stopping.store(false);
    std::thread loop([&] { shard.run(); });
    const auto deadline =
        std::chrono::steady_clock::now() + std::chrono::seconds(2);
    bool ok = false;
    while (!(ok = done()) && std::chrono::steady_clock::now() < deadline) {
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
    }
    stopping.store(true);  // seen within the poller's 100 ms
    loop.join();
    return ok;
  };
  std::string got;
  bool ended = false;
  auto read_link = [&] {
    char buf[4096];
    for (ssize_t r; (r = ::recv(link, buf, sizeof(buf), MSG_DONTWAIT)) >= 0;) {
      if (r == 0) {
        ended = true;
        break;
      }
      got.append(buf, (size_t)r);
    }
  };
  // Accepts the link: the shard's first token.
  CHECK(run_until([&] { return shard.conns_open.load() == 1; }));
  auto push = [&](pbft::LoopCmd::Kind kind, const std::string& bytes) {
    pbft::LoopCmd c;
    c.kind = kind;
    c.conn_id = 1;
    c.bytes = bytes;
    shard.push(std::move(c), /*force=*/true);
  };
  std::string want;
  constexpr int kWrites = 40;
  for (int i = 0; i < kWrites; ++i) {
    const std::string framed = pbft::frame_payload(
        "{\"type\":\"client-reply\",\"n\":" + std::to_string(i) + "}");
    want += framed;
    push(pbft::LoopCmd::kWriteConn, framed);
  }
  CHECK(run_until([&] {
    read_link();
    return got.size() >= want.size();
  }));
  CHECK(got == want && !ended);
  CHECK(shards.frames_out() == kWrites);
  CHECK(shards.send_calls() == 1);
  // The flush behind the stretch is the shard's `send` stage's too.
  const int64_t send_ns = shard.clock.clock.ns[pbft::kLoopSend];
  CHECK(send_ns > 0);
  // Writes and then a close in one stretch: the bytes, then the end.
  for (int i = 0; i < 3; ++i) {
    const std::string framed = pbft::frame_payload("last-" + std::to_string(i));
    want += framed;
    push(pbft::LoopCmd::kWriteConn, framed);
  }
  push(pbft::LoopCmd::kCloseConn, "");
  push(pbft::LoopCmd::kWriteConn, pbft::frame_payload("too late"));
  CHECK(run_until([&] {
    read_link();
    return ended;
  }));
  CHECK(got == want);  // closed behind them
  CHECK(shards.frames_out() == kWrites + 3);
  CHECK(shards.send_calls() == 2);
  CHECK(shard.clock.clock.ns[pbft::kLoopSend] > send_ns);
  ::close(link);
}

// ISSUE 14: MAC-vector codec units + the authenticator/tentative mode
// on a real-socket cluster — single loop AND the sharded front end —
// must reach the same executed state as signature mode.
void test_mac_codec_native() {
  pbft::Prepare p;
  p.view = 3;
  p.seq = 9;
  p.digest = std::string(64, 'a');
  p.replica = 2;
  p.sig = std::string(128, 'c');
  std::vector<pbft::MacLane> lanes(2);
  lanes[0].rid = 0;
  lanes[1].rid = 3;
  for (int i = 0; i < 16; ++i) lanes[1].tag[i] = (uint8_t)i;
  std::string frame;
  CHECK(pbft::message_to_binary_mac(pbft::Message(p), lanes, &frame));
  CHECK(pbft::payload_is_mac_frame(frame));
  auto back = pbft::message_from_binary(frame);
  CHECK(back.has_value());
  CHECK(pbft::message_canonical(*back) ==
        pbft::message_canonical(pbft::Message(p)));
  uint8_t tag[16];
  CHECK(pbft::mac_frame_lane(frame, 3, tag));
  CHECK(tag[5] == 5);
  CHECK(!pbft::mac_frame_lane(frame, 7, tag));  // no lane: sig fallback
  // malformed vectors reject
  CHECK(!pbft::message_from_binary(frame.substr(0, frame.size() - 2))
             .has_value());
  std::string bad = frame;
  bad.back() = (char)77;  // count past the bound
  CHECK(!pbft::message_from_binary(bad).has_value());
  // lane tag parity with the keyed primitive
  uint8_t key[32] = {0};
  uint8_t signable[32] = {0};
  uint8_t t1[16], t2[16];
  pbft::mac_tag(key, signable, t1);
  pbft::mac_tag(key, signable, t2);
  CHECK(pbft::mac_tag_equal(t1, t2));
  t2[0] ^= 1;
  CHECK(!pbft::mac_tag_equal(t1, t2));
  // tentative reply flag: omitted when 0 (byte-compat), signed when 1
  pbft::ClientReply r0;
  r0.view = 0;
  r0.timestamp = 1;
  r0.client = "c";
  r0.replica = 0;
  r0.result = "x";
  r0.sig = std::string(128, 'a');
  pbft::ClientReply r1 = r0;
  r1.tentative = 1;
  const std::string c0 = pbft::message_canonical(pbft::Message(r0));
  const std::string c1 = pbft::message_canonical(pbft::Message(r1));
  CHECK(c0.find("tentative") == std::string::npos);
  CHECK(c1.find("\"tentative\":1") != std::string::npos);
  uint8_t d0[32], d1[32];
  pbft::message_signable(pbft::Message(r0), d0);
  pbft::message_signable(pbft::Message(r1), d1);
  CHECK(std::memcmp(d0, d1, 32) != 0);  // the flag is signed content
  auto rt = pbft::from_payload(c1);
  CHECK(rt.has_value() && std::get<pbft::ClientReply>(*rt).tentative == 1);
}

void test_fastpath_mac_parity() {
  const int64_t sig = multicore_round(1, /*fastpath_mac=*/false);
  const int64_t mac1 =
      multicore_round(1, /*fastpath_mac=*/true, /*tentative=*/true);
  const int64_t mac2 =
      multicore_round(2, /*fastpath_mac=*/true, /*tentative=*/true);
  // The fast path changes how frames authenticate and when replies
  // leave, never what the cluster decides.
  CHECK(sig == 2);
  CHECK(mac1 == sig);
  CHECK(mac2 == sig);
}

void test_flight_recorder() {
  pbft::FlightRecorder fl;
  // Disabled (unconfigured) recorder: record is a no-op, dump refuses.
  fl.record(pbft::kFlightExecuted, 0, 1, -1);
  CHECK(fl.total_recorded() == 0);
  CHECK(fl.dump("/tmp/pbft-core-test-flight.bin") == -1);
  // Ring semantics: capacity 4, six records -> the oldest two evicted,
  // snapshot chronological.
  fl.configure(4);
  for (int i = 1; i <= 6; ++i) {
    fl.record(pbft::kFlightExecuted, 0, i, -1);
  }
  auto snap = fl.snapshot();
  CHECK(snap.size() == 4);
  CHECK(snap.front().seq == 3 && snap.back().seq == 6);
  for (size_t i = 1; i < snap.size(); ++i) {
    CHECK(snap[i].t_ns >= snap[i - 1].t_ns);
    CHECK(snap[i].ev == pbft::kFlightExecuted);
  }
  // Dump round-trip: header + 20-byte little-endian records (the format
  // pbft_tpu/utils/flight.py decodes byte-for-byte; the Python tier-1
  // test pins the cross-runtime parity through capi).
  const char* path = "/tmp/pbft-core-test-flight.bin";
  CHECK(fl.dump(path) == 4);
  FILE* f = std::fopen(path, "rb");
  CHECK(f != nullptr);
  if (f) {
    uint8_t buf[16 + 4 * 20];
    CHECK(std::fread(buf, 1, sizeof(buf), f) == sizeof(buf));
    std::fclose(f);
    CHECK(std::memcmp(buf, "PBFTBBX1", 8) == 0);
    CHECK(buf[8] == 1 && buf[12] == 4);  // version=1, count=4 (LE)
    // First record's seq field (offset 16 in the record) is 3.
    CHECK(buf[16 + 16] == 3);
  }
  std::remove(path);
  // disable() stops recording without dropping what is already there.
  fl.disable();
  fl.record(pbft::kFlightExecuted, 0, 99, -1);
  CHECK(fl.total_recorded() == 6);
}

void test_wal_roundtrip() {
  // Durable recovery (ISSUE 15). The golden bytes here are ALSO pinned
  // by tests/test_wal.py test_record_golden_bytes against the Python
  // encoder — the two on-disk formats cannot drift without one pin
  // going red.
  const std::string dir =
      "/tmp/pbft-core-test-wal-" + std::to_string((long)::getpid());
  ::mkdir(dir.c_str(), 0755);
  const std::string path = dir + "/replica-0.wal";
  std::remove(path.c_str());
  {
    pbft::Wal wal;
    CHECK(wal.open(path, /*do_fsync=*/false));
    wal.note_view(3, true, 4);
    // The same "ab"*32 digest the Python golden test writes.
    std::string ab;
    for (int i = 0; i < 32; ++i) ab += "ab";
    CHECK(wal.note_vote(pbft::kWalVotePrepare, 3, 17, ab));
    CHECK(wal.note_vote(pbft::kWalVotePrepare, 3, 17, ab));  // idempotent
    CHECK(!wal.note_vote(pbft::kWalVotePrepare, 3, 17,
                         std::string(64, 'c')));  // contradiction refused
    wal.note_checkpoint(16, "PAYLOAD", "[]");
    wal.flush();  // checkpoint -> compaction: canonical file image
  }
  std::string data;
  {
    FILE* f = std::fopen(path.c_str(), "rb");
    CHECK(f != nullptr);
    char buf[4096];
    size_t n;
    while ((n = std::fread(buf, 1, sizeof(buf), f)) > 0) data.append(buf, n);
    std::fclose(f);
  }
  // Golden image: header + view + checkpoint + the surviving vote.
  CHECK(data.size() == 12 + 22 + (5 + 8 + 4 + 7 + 4 + 2) + 54);
  CHECK(std::memcmp(data.data(), "PBFTWAL1", 8) == 0);
  CHECK((uint8_t)data[8] == 1);                     // version (LE)
  CHECK((uint8_t)data[12] == pbft::kWalRecView);    // tag
  CHECK((uint8_t)data[17] == 3);                    // view (LE i64)
  CHECK((uint8_t)data[25] == 1);                    // in_view_change
  CHECK((uint8_t)data[26] == 4);                    // pending view
  size_t off = 12 + 22;
  CHECK((uint8_t)data[off] == pbft::kWalRecCheckpoint);
  CHECK((uint8_t)data[off + 5] == 16);              // seq
  CHECK(data.substr(off + 17, 7) == "PAYLOAD");
  CHECK(data.substr(off + 28, 2) == "[]");
  off += 5 + 8 + 4 + 7 + 4 + 2;
  CHECK((uint8_t)data[off] == pbft::kWalRecVote);
  CHECK((uint8_t)data[off + 5] == pbft::kWalVotePrepare);
  CHECK((uint8_t)data[off + 14] == 17);             // seq
  CHECK((uint8_t)data[off + 22] == 0xAB);           // raw digest byte
  // Replay: guards re-arm, checkpoint + vote recovered, torn tail
  // (partial record appended by a mid-write kill) tolerated.
  {
    pbft::WalState st;
    CHECK(pbft::wal_decode(data, &st));
    CHECK(st.view == 3 && st.in_view_change && st.pending_view == 4);
    CHECK(st.has_checkpoint && st.checkpoint_seq == 16);
    CHECK(st.checkpoint_payload == "PAYLOAD");
    CHECK(st.votes.size() == 1);
    std::string torn = data;
    torn.push_back((char)pbft::kWalRecVote);
    torn.append("\x31\x00\x00\x00xx", 6);  // claims 49 bytes, has 2
    pbft::WalState st2;
    CHECK(pbft::wal_decode(torn, &st2));
    CHECK(st2.votes.size() == 1);
    pbft::WalState bad;
    CHECK(!pbft::wal_decode(std::string("NOTAWAL0") + std::string(8, '\0'),
                            &bad));
  }
  {
    pbft::Wal wal2;
    CHECK(wal2.open(path, false));
    CHECK(!wal2.recovered().empty());
    CHECK(!wal2.note_vote(pbft::kWalVotePrepare, 3, 17,
                          std::string(64, 'c')));
  }
  std::remove(path.c_str());
  ::rmdir(dir.c_str());
  // End to end: a wal-backed MiniCluster persists votes + checkpoints
  // through real rounds, and a restarted twin of replica 3 reinstalls
  // the stable checkpoint, re-joins the same view, and refuses to
  // contradict any persisted vote.
  {
    std::vector<std::vector<uint8_t>> seeds;
    auto cfg = test_config(&seeds);
    cfg.checkpoint_interval = 4;
    const std::string dir2 =
        "/tmp/pbft-core-test-wal2-" + std::to_string((long)::getpid());
    ::mkdir(dir2.c_str(), 0755);
    MiniCluster c(cfg, seeds);
    std::vector<std::unique_ptr<pbft::Wal>> wals;
    for (int i = 0; i < 4; ++i) {
      wals.push_back(std::make_unique<pbft::Wal>());
      CHECK(wals[i]->open(
          dir2 + "/replica-" + std::to_string(i) + ".wal", false));
      c.replicas[i].set_wal(wals[i].get());
    }
    for (int t = 1; t <= 6; ++t) {
      pbft::ClientRequest req;
      req.operation = "op-" + std::to_string(t);
      req.timestamp = t;
      req.client = "127.0.0.1:9000";
      c.emit(0, c.replicas[0].on_client_request(req));
      c.run();
      for (auto& w : wals) w->flush();  // the runtimes' emit-boundary
    }
    CHECK(c.replicas[3].executed_upto() == 6);
    CHECK(c.replicas[3].low_mark() == 4);  // stable checkpoint persisted
    const std::string chain3 = c.replicas[3].state_digest_hex();
    // "Crash" replica 3: reopen its log cold and restore a fresh twin.
    const std::string wpath = dir2 + "/replica-3.wal";
    pbft::Wal wal3;
    CHECK(wal3.open(wpath, false));
    CHECK(wal3.recovered().has_checkpoint);
    CHECK(wal3.recovered().checkpoint_seq == 4);
    CHECK(!wal3.recovered().votes.empty());  // seqs 5-6 survive the prune
    pbft::Replica twin(cfg, 3, seeds[3].data());
    twin.set_wal(&wal3);
    CHECK(twin.restore_from_wal(wal3.recovered()));
    CHECK(twin.executed_upto() == 4);  // the checkpoint floor
    CHECK(twin.low_mark() == 4);
    CHECK(twin.view() == 0);  // the SAME view
    CHECK(twin.state_digest_hex() != chain3);  // floor, not head...
    CHECK(twin.state_digest_hex() !=
          std::string(64, '0'));  // ...but a real restored chain
    for (int i = 0; i < 4; ++i) {
      std::remove((dir2 + "/replica-" + std::to_string(i) + ".wal").c_str());
    }
    ::rmdir(dir2.c_str());
  }
}

// Every test, in order; with arguments, only the tests named (their names
// without the test_ prefix), so that a case can count on its own in tier-1.
int main(int argc, char** argv) {
  const std::pair<const char*, void (*)()> tests[] = {
      {"sha512_vectors", test_sha512_vectors},
      {"blake2b_vector", test_blake2b_vector},
      {"ed25519_rfc8032", test_ed25519_rfc8032},
      {"scalar_reduction_vs_long_division", test_scalar_reduction_vs_long_division},
      {"crypto_yardstick", test_crypto_yardstick},
      {"canonical_json", test_canonical_json},
      {"secure_channel_native", test_secure_channel_native},
      {"four_replica_commit", test_four_replica_commit},
      {"batched_round_native", test_batched_round_native},
      {"view_change_native", test_view_change_native},
      {"stable_digest_majority_native", test_stable_digest_majority_native},
      {"state_transfer_native", test_state_transfer_native},
      {"span_delivered_while_next_on_wire", test_span_delivered_while_next_on_wire},
      {"pre_authenticated_waits_for_span_on_wire", test_pre_authenticated_waits_for_span_on_wire},
      {"rejected_signature_in_kept_span", test_rejected_signature_in_kept_span},
      {"batch_verify_rlc", test_batch_verify_rlc},
      {"verify_pool_native", test_verify_pool_native},
      {"remote_verifier_async", test_remote_verifier_async},
      {"remote_verifier_readiness", test_remote_verifier_readiness},
      {"net_backend_parity", test_net_backend_parity},
      {"multicore_parity", test_multicore_parity},
      {"wake_fd_keeps_every_wake", test_wake_fd_keeps_every_wake},
      {"multicore_burst", test_multicore_burst},
      {"loop_launches_ahead_of_kept_verdicts", test_loop_launches_ahead_of_kept_verdicts},
      {"loop_wedge_deadline_keeps_order", test_loop_wedge_deadline_keeps_order},
      {"loop_transport_failure_keeps_order", test_loop_transport_failure_keeps_order},
      {"loop_clock_unit", test_loop_clock_unit},
      {"loop_clock_on_the_loop", test_loop_clock_on_the_loop},
      {"emit_sends_once_a_connection", test_emit_sends_once_a_connection},
      {"flushed_streams_are_the_queued_frames", test_flushed_streams_are_the_queued_frames},
      {"one_frame_in_an_emit_is_one_send", test_one_frame_in_an_emit_is_one_send},
      {"no_byte_waits_for_a_later_emit", test_no_byte_waits_for_a_later_emit},
      {"wal_flush_precedes_an_emits_first_byte", test_wal_flush_precedes_an_emits_first_byte},
      {"connection_closed_by_failed_send_is_passed_over", test_connection_closed_by_failed_send_is_passed_over},
      {"shard_sends_once_a_drained_stretch", test_shard_sends_once_a_drained_stretch},
      {"mac_codec_native", test_mac_codec_native},
      {"fastpath_mac_parity", test_fastpath_mac_parity},
      {"flight_recorder", test_flight_recorder},
      {"wal_roundtrip", test_wal_roundtrip},
  };
  for (int i = 1; i < argc; ++i) {
    bool known = false;
    for (const auto& t : tests) known = known || std::strcmp(t.first, argv[i]) == 0;
    if (!known) {
      std::fprintf(stderr, "no test named %s\n", argv[i]);
      return 2;
    }
  }
  for (const auto& [name, run] : tests) {
    bool wanted = argc == 1;
    for (int i = 1; i < argc; ++i) wanted = wanted || std::strcmp(name, argv[i]) == 0;
    if (wanted) run();
  }
  if (g_failures) {
    std::fprintf(stderr, "%d failure(s)\n", g_failures);
    return 1;
  }
  std::printf("all native tests passed\n");
  return 0;
}

#include "secure.h"

#include <sys/random.h>

#include <cstdio>
#include <cstdlib>
#include <cstring>

#include "blake2b.h"
#include "ed25519.h"
#include "messages.h"  // to_hex / from_hex / kCodecBinary2

namespace pbft {

namespace {
bool wire_json_forced() {
  static const bool forced = [] {
    const char* v = std::getenv("PBFT_WIRE_CODEC");
    return v != nullptr && std::strcmp(v, "json") == 0;
  }();
  return forced;
}

// PBFT_PROTO_CAP=1.2.0 advertises the 1.2.0 hello with no fast-path
// offer — the interop-test lever simulating a pre-1.3.0 peer.
bool proto_capped_12() {
  static const bool capped = [] {
    const char* v = std::getenv("PBFT_PROTO_CAP");
    return v != nullptr && std::strcmp(v, "1.2.0") == 0;
  }();
  return capped;
}
}  // namespace

const char* wire_hello_version() {
  if (wire_json_forced()) return kProtocolVersionLegacy;
  if (proto_capped_12()) return kProtocolVersionBatch;
  return kProtocolVersion;
}

bool wire_offer_binary() { return !wire_json_forced(); }

bool wire_offer_mac(bool fastpath_mac) {
  return fastpath_mac && !wire_json_forced() && !proto_capped_12();
}

bool hello_offers_binary(const Json& obj) {
  if (!wire_offer_binary()) return false;
  const Json* codecs = obj.find("codecs");
  if (!codecs || !codecs->is_array()) return false;
  for (const Json& c : codecs->as_array()) {
    if (c.is_string() && c.as_string() == kCodecBinary2) return true;
  }
  return false;
}

bool hello_offers_mac(const Json& obj) {
  const Json* auth = obj.find("auth");
  if (!auth || !auth->is_array()) return false;
  for (const Json& a : auth->as_array()) {
    if (a.is_string() && a.as_string() == kAuthModeMac) return true;
  }
  return false;
}

void mac_tag(const uint8_t key[32], const uint8_t signable[32],
             uint8_t out[kMacTagLen]) {
  std::string data = kMacContext;
  data.append((const char*)signable, 32);
  blake2b_keyed(out, kMacTagLen, key, 32, (const uint8_t*)data.data(),
                data.size());
}

bool mac_tag_equal(const uint8_t a[kMacTagLen], const uint8_t b[kMacTagLen]) {
  uint8_t acc = 0;
  for (size_t i = 0; i < kMacTagLen; ++i) acc |= a[i] ^ b[i];
  return acc == 0;
}

namespace {

constexpr const char* kHsContext = "pbft-tpu-hs1|";
constexpr const char* kKdfContext = "pbft-tpu-k1|";

void fill_random(uint8_t* out, size_t n) {
  size_t off = 0;
  int failures = 0;
  while (off < n) {
    ssize_t r = getrandom(out + off, n - off, 0);
    if (r > 0) {
      off += (size_t)r;
      continue;
    }
    // getrandom unavailable/interrupted: /dev/urandom fallback.
    size_t got = 0;
    FILE* f = std::fopen("/dev/urandom", "rb");
    if (f) {
      got = std::fread(out + off, 1, n - off, f);
      std::fclose(f);
    }
    off += got;
    if (got == 0 && ++failures >= 16) {
      // No entropy source at all (e.g. a chroot without device nodes):
      // fail closed with a diagnostic — a CSPRNG-less handshake must
      // never proceed, and a silent spin here would look like a hang.
      std::fprintf(stderr,
                   "pbft secure: no entropy source (getrandom and "
                   "/dev/urandom both failed); aborting\n");
      std::abort();
    }
  }
}

// The AEAD counter is protocol data (nonce prefix + MAC input): serialize
// it explicitly little-endian so the byte compatibility with the Python
// runtime (net/secure.py uses int.to_bytes(..., "little")) holds on
// big-endian hosts too — a raw memcpy of the uint64 would silently fail
// every cross-runtime tag check there.
void store64_le(uint8_t out[8], uint64_t v) {
  for (int i = 0; i < 8; ++i) out[i] = (uint8_t)(v >> (8 * i));
}

// Same for the keystream block counter (secure.py: j.to_bytes(4, "little")).
void store32_le(uint8_t out[4], uint32_t v) {
  for (int i = 0; i < 4; ++i) out[i] = (uint8_t)(v >> (8 * i));
}

// key_dir = keyed-BLAKE2b(shared, "pbft-tpu-k1|" label "|" eph_i "|" eph_r).
void derive_key(uint8_t out[64], const uint8_t shared[32], const char* label,
                const uint8_t eph_i[32], const uint8_t eph_r[32]) {
  std::string data = kKdfContext;
  data += label;
  data += '|';
  data.append((const char*)eph_i, 32);
  data += '|';
  data.append((const char*)eph_r, 32);
  blake2b_keyed(out, 64, shared, 32, (const uint8_t*)data.data(), data.size());
}

// 32-byte authenticator key: the same KDF shape at digest size 32
// (net/secure.py derive_auth_keys).
void derive_auth_key32(uint8_t out[32], const uint8_t shared[32],
                       const char* label, const uint8_t eph_i[32],
                       const uint8_t eph_r[32]) {
  std::string data = kKdfContext;
  data += label;
  data += '|';
  data.append((const char*)eph_i, 32);
  data += '|';
  data.append((const char*)eph_r, 32);
  blake2b_keyed(out, 32, shared, 32, (const uint8_t*)data.data(), data.size());
}

bool ct_equal(const uint8_t* a, const uint8_t* b, size_t n) {
  uint8_t acc = 0;
  for (size_t i = 0; i < n; ++i) acc |= a[i] ^ b[i];
  return acc == 0;
}

}  // namespace

void derive_auth_keys(uint8_t a_i2r[32], uint8_t a_r2i[32],
                      const uint8_t shared[32], const uint8_t eph_i[32],
                      const uint8_t eph_r[32]) {
  derive_auth_key32(a_i2r, shared, "a-i2r", eph_i, eph_r);
  derive_auth_key32(a_r2i, shared, "a-r2i", eph_i, eph_r);
}

std::string aead_seal(const uint8_t key[64], uint64_t ctr,
                      const std::string& plaintext) {
  uint8_t nonce[12];
  store64_le(nonce, ctr);
  std::string out = plaintext;
  uint8_t block[64];
  for (size_t j = 0; j * 64 < plaintext.size(); ++j) {
    store32_le(nonce + 8, (uint32_t)j);
    blake2b_keyed(block, 64, key, 32, nonce, 12);
    size_t n = std::min<size_t>(64, plaintext.size() - j * 64);
    for (size_t k = 0; k < n; ++k) out[j * 64 + k] ^= block[k];
  }
  std::string macin;
  macin.append((const char*)nonce, 8);
  macin += out;
  uint8_t tag[kTagLen];
  blake2b_keyed(tag, kTagLen, key + 32, 32, (const uint8_t*)macin.data(),
                macin.size());
  out.append((const char*)tag, kTagLen);
  return out;
}

std::optional<std::string> aead_open(const uint8_t key[64], uint64_t ctr,
                                     const std::string& sealed) {
  if (sealed.size() < kTagLen) return std::nullopt;
  std::string ct = sealed.substr(0, sealed.size() - kTagLen);
  uint8_t ctr_le[8];
  store64_le(ctr_le, ctr);
  std::string macin;
  macin.append((const char*)ctr_le, 8);
  macin += ct;
  uint8_t tag[kTagLen];
  blake2b_keyed(tag, kTagLen, key + 32, 32, (const uint8_t*)macin.data(),
                macin.size());
  if (!ct_equal(tag, (const uint8_t*)sealed.data() + ct.size(), kTagLen))
    return std::nullopt;
  uint8_t nonce[12];
  store64_le(nonce, ctr);
  uint8_t block[64];
  for (size_t j = 0; j * 64 < ct.size(); ++j) {
    store32_le(nonce + 8, (uint32_t)j);
    blake2b_keyed(block, 64, key, 32, nonce, 12);
    size_t n = std::min<size_t>(64, ct.size() - j * 64);
    for (size_t k = 0; k < n; ++k) ct[j * 64 + k] ^= block[k];
  }
  return ct;
}

SecureChannel::SecureChannel(const ClusterConfig* cfg, int64_t my_id,
                             const uint8_t identity_seed[32], bool initiator,
                             int64_t expected_peer, bool offer_mac,
                             bool auth_only)
    : cfg_(cfg),
      my_id_(my_id),
      initiator_(initiator),
      expected_peer_(expected_peer),
      offer_mac_(offer_mac),
      auth_only_(auth_only),
      hs_version_(wire_hello_version()) {
  std::memcpy(seed_, identity_seed, 32);
  fill_random(eph_secret_, 32);
  ed25519_dh_public(eph_pub_, eph_secret_);
}

bool SecureChannel::check_version(const Json& obj, std::string* err) {
  const Json* v = obj.find("ver");
  std::string ver = v && v->is_string() ? v->as_string() : "<none>";
  // Compatible set, not exact match: 1.1.0 only ADDS the negotiated
  // binary codec, 1.2.0 the batched pre-prepare (batch=1 frames are
  // byte-identical), and 1.3.0 the offer-gated fast-path modes, so
  // older peers interoperate (JSON both ways for 1.0.0; bin2 batch=1
  // for 1.1.0; signature mode for pre-1.3.0).
  if (ver != kProtocolVersion && ver != kProtocolVersionBatch &&
      ver != kProtocolVersionBin2 && ver != kProtocolVersionLegacy) {
    *err = "protocol version mismatch: peer speaks '" + ver +
           "', this node speaks '" + kProtocolVersion + "'";
    return false;
  }
  return true;
}

void SecureChannel::transcript(uint8_t out[32]) const {
  const uint8_t* eph_i = initiator_ ? eph_pub_ : peer_eph_;
  const uint8_t* eph_r = initiator_ ? peer_eph_ : eph_pub_;
  std::string data = kHsContext;
  data += hs_version_;
  data += '|';
  data.append((const char*)eph_i, 32);
  data += '|';
  data.append((const char*)eph_r, 32);
  blake2b(out, 32, (const uint8_t*)data.data(), data.size());
}

bool SecureChannel::verify_peer_sig(const Json& obj, const char* label) {
  const Json* node = obj.find("node");
  if (!node || !node->is_int()) {
    error_ = "handshake frame without node id";
    return false;
  }
  int64_t n = node->as_int();
  if (expected_peer_ >= 0 && n != expected_peer_) {
    error_ = "peer claims node " + std::to_string(n) + ", expected " +
             std::to_string(expected_peer_);
    return false;
  }
  if (n < 0 || n >= cfg_->n()) {
    error_ = "unknown node id " + std::to_string(n);
    return false;
  }
  const Json* sig = obj.find("sig");
  uint8_t sigbytes[64];
  if (!sig || !sig->is_string() || !from_hex(sig->as_string(), sigbytes, 64)) {
    error_ = "handshake frame without signature";
    return false;
  }
  uint8_t th[32];
  transcript(th);
  std::string msg((const char*)th, 32);
  msg += label;
  if (!ed25519_verify(cfg_->replicas[n].pubkey, (const uint8_t*)msg.data(),
                      msg.size(), sigbytes)) {
    error_ = "bad handshake signature from node " + std::to_string(n);
    return false;
  }
  peer_id_ = n;
  return true;
}

bool SecureChannel::finish() {
  uint8_t shared[32];
  if (!ed25519_dh_shared(shared, eph_secret_, peer_eph_)) {
    error_ = "invalid ephemeral key from peer";
    return false;
  }
  const uint8_t* eph_i = initiator_ ? eph_pub_ : peer_eph_;
  const uint8_t* eph_r = initiator_ ? peer_eph_ : eph_pub_;
  uint8_t k_i2r[64], k_r2i[64];
  derive_key(k_i2r, shared, "i2r", eph_i, eph_r);
  derive_key(k_r2i, shared, "r2i", eph_i, eph_r);
  std::memcpy(send_key_, initiator_ ? k_i2r : k_r2i, 64);
  std::memcpy(recv_key_, initiator_ ? k_r2i : k_i2r, 64);
  // Authenticator session keys (ISSUE 14): same transcript material,
  // distinct labels — lanes and frame sealing never share key bytes.
  // Byte-identical to net/secure.py derive_auth_keys.
  uint8_t a_i2r[32], a_r2i[32];
  derive_auth_keys(a_i2r, a_r2i, shared, eph_i, eph_r);
  std::memcpy(auth_send_key_, initiator_ ? a_i2r : a_r2i, 32);
  std::memcpy(auth_recv_key_, initiator_ ? a_r2i : a_i2r, 32);
  established_ = true;
  return true;
}

namespace {
// Codec offer attached to every hello this node emits (unless JSON is
// forced): the receiver may then send binary-v2 hot-message frames back
// on its own dialed link, and the dialing side reads the responder's
// offer to pick this link's codec. The fast-path auth offer (ISSUE 14)
// rides the same hello under the "auth" key.
void attach_codecs(JsonObject* o, bool offer_mac = false) {
  if (wire_offer_binary()) {
    JsonArray codecs;
    codecs.push_back(Json(kCodecBinary2));
    (*o)["codecs"] = Json(std::move(codecs));
  }
  if (wire_offer_mac(offer_mac)) {
    JsonArray auth;
    auth.push_back(Json(kAuthModeMac));
    (*o)["auth"] = Json(std::move(auth));
  }
}
}  // namespace

std::string SecureChannel::initiator_hello() {
  JsonObject o;
  o["type"] = Json("hello");
  o["ver"] = Json(wire_hello_version());
  o["node"] = Json(my_id_);
  o["eph"] = Json(to_hex(eph_pub_, 32));
  attach_codecs(&o, offer_mac_);
  return Json(o).dump();
}

std::optional<std::string> SecureChannel::on_hello(const Json& obj) {
  if (!check_version(obj, &error_)) return std::nullopt;
  const Json* eph = obj.find("eph");
  if (!eph || !eph->is_string() ||
      !from_hex(eph->as_string(), peer_eph_, 32)) {
    error_ =
        "plaintext peer rejected: this cluster requires encrypted links "
        "(hello carried no ephemeral key)";
    return std::nullopt;
  }
  // Responder: the transcript binds to the initiator's advertised
  // version (check_version admitted it into the compatible set).
  const Json* ver = obj.find("ver");
  if (ver && ver->is_string()) hs_version_ = ver->as_string();
  peer_offers_mac_ = pbft::hello_offers_mac(obj);
  have_peer_eph_ = true;
  uint8_t th[32];
  transcript(th);
  std::string msg((const char*)th, 32);
  msg += "|resp";
  uint8_t sig[64];
  ed25519_sign(sig, seed_, (const uint8_t*)msg.data(), msg.size());
  JsonObject o;
  o["type"] = Json("hello");
  o["ver"] = Json(wire_hello_version());
  o["node"] = Json(my_id_);
  o["eph"] = Json(to_hex(eph_pub_, 32));
  o["sig"] = Json(to_hex(sig, 64));
  attach_codecs(&o, offer_mac_);
  return Json(o).dump();
}

std::optional<std::string> SecureChannel::on_hello_reply(const Json& obj) {
  const Json* type = obj.find("type");
  if (type && type->is_string() && type->as_string() == "reject") {
    const Json* r = obj.find("reason");
    error_ = "peer rejected handshake: " +
             (r && r->is_string() ? r->as_string() : "<no reason>");
    return std::nullopt;
  }
  if (!check_version(obj, &error_)) return std::nullopt;
  const Json* eph = obj.find("eph");
  if (!eph || !eph->is_string() ||
      !from_hex(eph->as_string(), peer_eph_, 32)) {
    error_ = "responder hello carried no ephemeral key";
    return std::nullopt;
  }
  peer_offers_mac_ = pbft::hello_offers_mac(obj);
  have_peer_eph_ = true;
  if (!verify_peer_sig(obj, "|resp")) return std::nullopt;
  uint8_t th[32];
  transcript(th);
  std::string msg((const char*)th, 32);
  msg += "|init";
  uint8_t sig[64];
  ed25519_sign(sig, seed_, (const uint8_t*)msg.data(), msg.size());
  if (!finish()) return std::nullopt;
  JsonObject o;
  o["type"] = Json("auth");
  o["node"] = Json(my_id_);
  o["sig"] = Json(to_hex(sig, 64));
  return Json(o).dump();
}

bool SecureChannel::on_auth(const Json& obj) {
  if (!have_peer_eph_) {
    error_ = "auth before hello";
    return false;
  }
  if (!verify_peer_sig(obj, "|init")) return false;
  return finish();
}

std::string SecureChannel::seal_frame(const std::string& payload) {
  return aead_seal(send_key_, send_ctr_++, payload);
}

std::optional<std::string> SecureChannel::open_frame(
    const std::string& payload) {
  auto out = aead_open(recv_key_, recv_ctr_, payload);
  if (!out) {
    error_ = "AEAD tag mismatch on frame " + std::to_string(recv_ctr_) +
             " from node " + std::to_string(peer_id_);
    return std::nullopt;
  }
  ++recv_ctr_;
  return out;
}

std::string SecureChannel::reject_payload(const std::string& reason) {
  JsonObject o;
  o["type"] = Json("reject");
  o["reason"] = Json(reason);
  o["ver"] = Json(wire_hello_version());
  return Json(o).dump();
}

std::string SecureChannel::plain_hello(int64_t my_id, bool offer_mac) {
  JsonObject o;
  o["type"] = Json("hello");
  o["ver"] = Json(wire_hello_version());
  o["node"] = Json(my_id);
  attach_codecs(&o, offer_mac);
  return Json(o).dump();
}

}  // namespace pbft

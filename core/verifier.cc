#include "verifier.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <cstdio>
#include <cstdlib>
#include <cstring>

#include "ed25519.h"
#include "net.h"
#include "verify_pool.h"

namespace pbft {

std::vector<uint8_t> CpuVerifier::verify_batch(
    const std::vector<VerifyItem>& items) {
  // Pack into the batch layout and hand the batch to the process-wide
  // worker pool (core/verify_pool.cc): one RLC + Pippenger window per
  // worker lane instead of one Shamir ladder per signature, with the
  // serial path's exact accept set.
  const size_t n = items.size();
  std::vector<uint8_t> pubs(32 * n), msgs(32 * n), sigs(64 * n), out(n);
  for (size_t i = 0; i < n; ++i) {
    std::memcpy(pubs.data() + 32 * i, items[i].pub, 32);
    std::memcpy(msgs.data() + 32 * i, items[i].msg, 32);
    std::memcpy(sigs.data() + 64 * i, items[i].sig, 64);
  }
  global_verify_pool().verify(pubs.data(), msgs.data(), sigs.data(), n,
                              out.data());
  return out;
}

size_t CpuVerifier::parallel_capacity() const {
  return (size_t)global_verify_pool().threads();
}

RemoteVerifier::RemoteVerifier(std::string target) : target_(std::move(target)) {
  if (const char* e = std::getenv("PBFT_VERIFY_CONNECT_MS"))
    connect_timeout_ms_ = std::atoi(e) > 0 ? std::atoi(e) : connect_timeout_ms_;
  if (const char* e = std::getenv("PBFT_VERIFY_PROBE_MS"))
    probe_timeout_ms_ = std::atoi(e) > 0 ? std::atoi(e) : probe_timeout_ms_;
}

RemoteVerifier::~RemoteVerifier() {
  if (fd_ >= 0) ::close(fd_);
}

void RemoteVerifier::drop_connection() {
  if (fd_ >= 0) ::close(fd_);
  fd_ = -1;
  inflight_ = false;
  retry_after_ =
      std::chrono::steady_clock::now() + std::chrono::milliseconds(reprobe_ms_);
}

bool RemoteVerifier::connect_with_deadline() {
  if (!target_.empty() && target_[0] == '/') {
    // Unix-domain connect on the local host completes (or refuses)
    // immediately; the listen backlog cannot blackhole it.
    fd_ = ::socket(AF_UNIX, SOCK_STREAM, 0);
    if (fd_ < 0) return false;
    sockaddr_un addr{};
    addr.sun_family = AF_UNIX;
    std::strncpy(addr.sun_path, target_.c_str(), sizeof(addr.sun_path) - 1);
    if (::connect(fd_, (sockaddr*)&addr, sizeof(addr)) != 0) {
      ::close(fd_);
      fd_ = -1;
      return false;
    }
    return true;
  }
  bool in_progress = false;
  fd_ = dial_tcp_nb(target_, &in_progress);  // shared dialer (net.cc)
  if (fd_ < 0) return false;
  if (in_progress) {
    pollfd pfd{fd_, POLLOUT, 0};
    if (::poll(&pfd, 1, connect_timeout_ms_) <= 0) {
      ::close(fd_);
      fd_ = -1;
      return false;  // the short dial deadline: never stall the loop
    }
    int err = 0;
    socklen_t len = sizeof(err);
    if (::getsockopt(fd_, SOL_SOCKET, SO_ERROR, &err, &len) != 0 || err != 0) {
      ::close(fd_);
      fd_ = -1;
      return false;
    }
  }
  // The request/verdict exchange uses blocking writes/reads sized to the
  // send-buffer budget; restore blocking mode after the probing connect.
  int flags = ::fcntl(fd_, F_GETFL, 0);
  if (flags >= 0) ::fcntl(fd_, F_SETFL, flags & ~O_NONBLOCK);
  return true;
}

bool RemoteVerifier::probe_status(bool allow_legacy) {
  // Count-0 status probe (pbft_tpu/net/service.py pack_status): 8 bytes
  // 'V' 'S' version state u16be devices u16be warmed-shapes.
  const uint8_t probe[4] = {0, 0, 0, 0};
  if (::send(fd_, probe, 4, MSG_NOSIGNAL) != 4) return false;
  uint8_t status[8];
  size_t got = 0;
  while (got < sizeof(status)) {
    pollfd pfd{fd_, POLLIN, 0};
    int r = ::poll(&pfd, 1, probe_timeout_ms_);
    if (r <= 0) {
      if (got == 0 && allow_legacy) {
        // A pre-handshake service never answers count 0 (it maps to an
        // empty batch with an empty reply): remember the target as
        // legacy so later dials skip the probe deadline entirely. But
        // do NOT keep this link: the probe is still outstanding on it,
        // and a service that is merely SLOW (not legacy) would answer
        // it late — 8 status bytes mis-pairing with the next batch's
        // verdict stream, turning protocol framing into signature
        // verdicts (found by core/race_stress.cc under the sanitizer
        // matrix, ISSUE 8). The caller drops this connection and
        // re-dials a clean probe-free stream.
        legacy_ = true;
        state_ = ServiceState::kReady;
        devices_ = 0;
        warmed_ = 0;
        return false;
      }
      return false;  // wedged, or died mid-status
    }
    ssize_t n = ::recv(fd_, status + got, sizeof(status) - got, 0);
    if (n <= 0) return false;
    got += (size_t)n;
  }
  if (status[0] != 'V' || status[1] != 'S' || status[2] != 1 || status[3] > 2)
    return false;
  ServiceState prev = state_;
  state_ = status[3] == 0   ? ServiceState::kWarming
           : status[3] == 1 ? ServiceState::kReady
                            : ServiceState::kCpuOnly;
  devices_ = (status[4] << 8) | status[5];
  warmed_ = (status[6] << 8) | status[7];
  if (state_ != prev) {
    const char* names[] = {"unknown", "warming", "ready", "cpu-only"};
    std::fprintf(stderr, "[verifier] service %s: %s (%d devices, %d shapes)\n",
                 target_.c_str(), names[(int)state_], devices_, warmed_);
  }
  return true;
}

bool RemoteVerifier::ensure_connected() {
  auto now = std::chrono::steady_clock::now();
  if (fd_ >= 0) {
    if (state_ != ServiceState::kWarming) return true;
    // Warming: the connection is good but the accelerator isn't — ask
    // again at the reprobe cadence, serving from the fallback meanwhile.
    if (now < retry_after_) return false;
    retry_after_ = now + std::chrono::milliseconds(reprobe_ms_);
    if (!probe_status(/*allow_legacy=*/false)) {
      drop_connection();
      return false;
    }
    return state_ != ServiceState::kWarming;
  }
  if (now < retry_after_) return false;
  if (!connect_with_deadline()) {
    retry_after_ = now + std::chrono::milliseconds(reprobe_ms_);
    return false;
  }
  tune_send_budget();
  if (legacy_) {
    // Known pre-handshake target: the probe deadline was paid once on
    // the first dial; treat every reconnect as ready immediately.
    state_ = ServiceState::kReady;
    return true;
  }
  if (!probe_status(/*allow_legacy=*/true)) {
    drop_connection();
    if (legacy_) {
      // The probe just timed out and marked this target pre-handshake:
      // the dropped stream had the probe outstanding (a late answer
      // would mis-pair with verdict bytes), but the target itself is
      // reachable — re-dial a clean stream NOW and use it probe-free,
      // so a genuine legacy service still serves the first verify.
      retry_after_ = {};
      if (connect_with_deadline()) {
        tune_send_budget();
        state_ = ServiceState::kReady;
        return true;
      }
      retry_after_ = now + std::chrono::milliseconds(reprobe_ms_);
    }
    return false;
  }
  if (state_ == ServiceState::kWarming) {
    retry_after_ = now + std::chrono::milliseconds(reprobe_ms_);
    return false;
  }
  return true;
}

void RemoteVerifier::tune_send_budget() {
  // Best-effort: a roomier send buffer widens the async write budget
  // (the kernel clamps to wmem_max without privileges; harmless if so).
  // The async item budget is then DERIVED from what the kernel actually
  // granted — begin_batch's blocking write must always fit the buffer,
  // or the event loop would stall for exactly the round-trip the async
  // path exists to hide.
  int want = 1 << 20;
  ::setsockopt(fd_, SOL_SOCKET, SO_SNDBUF, &want, sizeof(want));
  int got = 0;
  socklen_t len = sizeof(got);
  if (::getsockopt(fd_, SOL_SOCKET, SO_SNDBUF, &got, &len) == 0 && got > 0) {
    // Linux reports the doubled value (bookkeeping overhead included);
    // budget on half of it, minus the 4-byte header.
    size_t payload = (size_t)got / 2;
    async_budget_items_ = payload > 132 ? (payload - 4) / 128 : 1;
    if (async_budget_items_ > 4096) async_budget_items_ = 4096;
  }
}

static bool write_all(int fd, const uint8_t* data, size_t n) {
  while (n > 0) {
    ssize_t w = ::write(fd, data, n);
    if (w <= 0) return false;
    data += w;
    n -= (size_t)w;
  }
  return true;
}

static bool read_all(int fd, uint8_t* data, size_t n) {
  while (n > 0) {
    ssize_t r = ::read(fd, data, n);
    if (r <= 0) return false;
    data += r;
    n -= (size_t)r;
  }
  return true;
}

static std::vector<uint8_t> encode_request(
    const std::vector<VerifyItem>& items) {
  const uint32_t n = (uint32_t)items.size();
  std::vector<uint8_t> buf(4 + (size_t)n * 128);
  buf[0] = (uint8_t)(n >> 24);
  buf[1] = (uint8_t)(n >> 16);
  buf[2] = (uint8_t)(n >> 8);
  buf[3] = (uint8_t)n;
  for (uint32_t i = 0; i < n; ++i) {
    uint8_t* p = buf.data() + 4 + (size_t)i * 128;
    std::memcpy(p, items[i].pub, 32);
    std::memcpy(p + 32, items[i].msg, 32);
    std::memcpy(p + 64, items[i].sig, 64);
  }
  return buf;
}

std::vector<uint8_t> RemoteVerifier::verify_on_host(
    const std::vector<VerifyItem>& items) {
  ++host_fallbacks_;
  return fallback_.verify_batch(items);
}

std::vector<uint8_t> RemoteVerifier::verify_batch(
    const std::vector<VerifyItem>& items) {
  if (items.empty()) return {};
  // A sync call with a batch still in flight would desync the
  // one-reply-per-request pairing on the connection: drop the link and
  // let both batches go through the fallback (callers never mix modes,
  // so this is belt-and-braces).
  if (inflight_) {
    ::close(fd_);
    fd_ = -1;
    inflight_ = false;
  }
  if (!ensure_connected()) return verify_on_host(items);
  auto buf = encode_request(items);
  std::vector<uint8_t> out(items.size());
  if (!write_all(fd_, buf.data(), buf.size()) ||
      !read_all(fd_, out.data(), out.size())) {
    // Killed mid-stream: drop the link (with reconnect backoff) and
    // verify THIS batch on the native pool — the liveness contract.
    drop_connection();
    return verify_on_host(items);
  }
  return out;
}

bool RemoteVerifier::begin_batch(const std::vector<VerifyItem>& items) {
  if (items.empty() || inflight_) return false;
  if (!ensure_connected()) return false;
  // Batches beyond the measured send-buffer budget take the caller's
  // synchronous path — the pre-async behavior, and rare (the service's
  // own merge cap is 4096).
  if (items.size() > async_budget_items_) return false;
  auto buf = encode_request(items);
  if (!write_all(fd_, buf.data(), buf.size())) {
    drop_connection();
    return false;
  }
  inflight_ = true;
  expect_ = items.size();
  resp_.clear();
  return true;
}

void RemoteVerifier::cancel_inflight() {
  if (!inflight_) return;
  // The wedge deadline fired: the connection may still be alive but the
  // verdicts never came. Closing it is the only safe reset — partial
  // verdict bytes already received would otherwise mis-pair with the
  // next batch on the same stream.
  if (fd_ >= 0) ::close(fd_);
  fd_ = -1;
  inflight_ = false;
  resp_.clear();
  expect_ = 0;
}

bool RemoteVerifier::poll_result(std::vector<uint8_t>* out, bool* failed) {
  *failed = false;
  if (!inflight_) {
    *failed = true;
    return true;
  }
  while (resp_.size() < expect_) {
    uint8_t chunk[4096];
    size_t want = expect_ - resp_.size();
    ssize_t r = ::recv(fd_, chunk, want < sizeof(chunk) ? want : sizeof(chunk),
                       MSG_DONTWAIT);
    if (r > 0) {
      resp_.insert(resp_.end(), chunk, chunk + r);
      continue;
    }
    if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      return false;  // more verdicts still on the wire; poll again
    }
    // EOF or error mid-batch: the service died — hand the batch back to
    // the caller's fallback (and back off reconnecting).
    drop_connection();
    *failed = true;
    return true;
  }
  inflight_ = false;
  *out = std::move(resp_);
  resp_ = {};
  return true;
}

}  // namespace pbft

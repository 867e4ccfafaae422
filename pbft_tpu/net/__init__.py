"""pbft_tpu.net — the host-side runtime glue around the native daemon.

``pbftd`` (core/) is the one program that is a replica on sockets; the
Python state machine in ``pbft_tpu.consensus`` under the simulator is the
reference it is held to.

- ``service``   — the verify service's wire protocol (the 128-byte-triple
  batches ``pbftd``'s RemoteVerifier ships, core/verifier.h, and the two
  status probes) and its dispatcher, which merges what every connection
  queued into one backend call. It knows no shape and no device; a bare
  ``VerifierService`` serves a host verifier and says ``cpu-only``.
- ``verify_service`` — the engine, the one way a served window reaches
  the chip (owns the accelerator, AOT-warms and times every pad-ladder
  shape, shards each window across all local devices); ``verifyd``, the
  one daemon, which joins engine and dispatcher and alone answers
  ``ready``.
- ``secure``    — encrypted replica links + protocol versioning
  (signed-ephemeral-DH handshake, keyed-BLAKE2b AEAD; what
  core/secure.cc is held to byte for byte — the reference's
  Noise-secured development_transport, reference src/main.rs:42), and the
  version the gateway's hello carries.
- ``gateway``   — the client-gateway tier: thousands of client
  connections onto one framed link a replica.
- ``client``    — the PBFT client: sends a raw-JSON request to the primary
  and collects dialed-back replies until f+1 match (PBFT §4.1; the
  reference's manual telnet + ``nc -kl`` walkthrough, README.md:5-43,
  scripted).
- ``launcher``  — spawns a localhost cluster of ``pbftd`` from a
  ClusterConfig (the reference ran 4 shells by hand).
"""

from .client import PbftClient
from .launcher import LocalCluster, pbftd_path
from .secure import PROTOCOL_VERSION, SecureChannel
from .service import VerifierService
from .verify_service import (
    ShardedVerifyEngine,
    VerifyServiceDaemon,
    probe_status,
    probe_status_json,
)

__all__ = [
    "PbftClient",
    "LocalCluster",
    "VerifierService",
    "VerifyServiceDaemon",
    "ShardedVerifyEngine",
    "probe_status",
    "probe_status_json",
    "SecureChannel",
    "PROTOCOL_VERSION",
    "pbftd_path",
]

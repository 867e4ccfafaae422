"""Plain reference of what a served request must come back as.

The deployment's operation is Castro & Liskov's 0/0 micro-benchmark
operation: a null argument, a null result. The program answers every such
operation with one fixed result string; a reply is signed over the
Blake2b-256 digest of its canonical JSON without the ``sig`` member. This
file restates both from the wire format and imports nothing of the program.
"""

from __future__ import annotations

import hashlib
import json

NULL_RESULT = "awesome!"


def execute(operation: str) -> str:
    """The 0/0 operation: whatever the argument, the null result."""
    del operation
    return NULL_RESULT


def reply_signable(reply: dict) -> bytes:
    """The 32 bytes a replica signs for a client reply: canonical JSON
    (sorted keys, no spaces) of the reply without ``sig`` and without any
    private key of the reader's own; ``tentative`` is omitted when zero."""
    body = {
        "type": "client-reply",
        "view": int(reply["view"]),
        "timestamp": int(reply["timestamp"]),
        "client": str(reply["client"]),
        "replica": int(reply["replica"]),
        "result": str(reply["result"]),
    }
    if reply.get("tentative"):
        body["tentative"] = int(reply["tentative"])
    blob = json.dumps(body, sort_keys=True, separators=(",", ":")).encode()
    return hashlib.blake2b(blob, digest_size=32).digest()


def quorum_result(replies: list, f: int, n: int, pubkeys: list, verify) -> str | None:
    """The result that f+1 distinct replicas signed as committed (or 2f+1
    in one view when some are tentative), or None. ``verify`` is the
    reference signature check; one vote per replica id."""
    votes: dict = {}
    for r in replies:
        rid = r.get("replica")
        if not isinstance(rid, int) or not 0 <= rid < n:
            continue
        try:
            sig = bytes.fromhex(r["sig"])
            ok = len(sig) == 64 and verify(pubkeys[rid], reply_signable(r), sig)
        except (KeyError, TypeError, ValueError):
            ok = False
        if ok:
            votes[rid] = (r["result"], r["view"], 1 if r.get("tentative") else 0)
    committed: dict = {}
    any_view: dict = {}
    for result, view, tentative in votes.values():
        any_view[(result, view)] = any_view.get((result, view), 0) + 1
        if not tentative:
            committed[result] = committed.get(result, 0) + 1
    for (result, _view), count in any_view.items():
        if count >= 2 * f + 1 or committed.get(result, 0) >= f + 1:
            return result
    return None

"""Crash-safe KV handoff between prefill and decode replicas (ISSUE 14).

Disaggregated serving splits one generation across two engines: a
prefill-role engine runs the prompt and samples the first token, then
EXPORTS the slot's K/V (page-granular ship buffers, trimmed to the true
prompt length) instead of keeping the slot; a decode-role engine IMPORTS
those bytes into its own pool and decodes the rest. The bytes in flight
between the two hosts are the crash surface this module owns:

- **Payloads** are self-verifying: :func:`build_payload` stamps a
  SHA-256 digest over the K/V bytes plus every replay-relevant field
  (prompt, first token, PRNG lane, seed, positions), and
  :func:`verify_payload` re-hashes on the importing side — a torn or
  corrupted transfer downgrades to a local re-prefill (the stream is a
  deterministic function of prompt+knobs+seed, so the fallback is
  token-identical), never a silently wrong cache.
- **Leases** bound every shipped payload's lifetime: the prefill engine
  grants an epoch-stamped lease per handoff and keeps the only pin on
  the shipped object. A decode replica that claims in time releases the
  pin; one that dies (or a router that falls back) simply never claims,
  and the lease sweep — run from the prefill engine's driver loop —
  reclaims the pin at expiry. A crash can therefore never pin the
  object plane: orphaned ship buffers free themselves on the lease
  clock.

The payload rides the existing object plane (``rt.put`` → chunked
multi-source shm pulls, the same machinery as the collective broadcast
path); descriptors — the small routing record carrying the lease, the
digest, and the replay fields — travel inline over the RPC plane.
"""
from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np


class HandoffError(RuntimeError):
    """A shipped KV payload could not be resolved or verified (lease
    expired and the object was reclaimed, bytes failed the digest, or
    the shipper died mid-transfer). Always recoverable: the descriptor
    carries prompt+seed, so the importer falls back to a local
    re-prefill that is token-identical by determinism."""


#: Payloads at or under this many bytes travel inline in the descriptor
#: (one RPC hop, no object-plane round trip); larger ones are put into
#: the object store once and pulled by the decode side via the chunked
#: transfer path.
SHIP_INLINE_MAX = 64 * 1024


def _meta_bytes(payload: Dict[str, Any]) -> bytes:
    return (f"pos={int(payload['pos'])};first={int(payload['first'])};"
            f"seed={int(payload['seed'])};"
            f"max_new={int(payload['max_new'])}").encode()


def payload_digest(payload: Dict[str, Any]) -> str:
    """SHA-256 over the shipped K/V bytes AND every replay-relevant
    field — byte-verification of the shipped pages, not just a length
    check. Deterministic across exporters' page sizes because every
    exporter trims to the true prompt length before hashing. Quantized
    payloads (ISSUE 16) additionally fold the per-page scales and the layout
    identity (``kv_dtype``, ``page_size``) into the hash — ONLY when
    present, so fp digests are byte-for-byte what they were before the
    int8 plane existed."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(payload["k"]).tobytes())
    h.update(np.ascontiguousarray(payload["v"]).tobytes())
    h.update(np.ascontiguousarray(
        np.asarray(payload["prompt"], np.int32)).tobytes())
    h.update(np.ascontiguousarray(
        np.asarray(payload["rng"], np.uint32)).tobytes())
    h.update(_meta_bytes(payload))
    if payload.get("ks") is not None:
        h.update(np.ascontiguousarray(
            np.asarray(payload["ks"], np.float32)).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(payload["vs"], np.float32)).tobytes())
        h.update((f"kv_dtype={payload.get('kv_dtype', 'int8')};"
                  f"page_size={int(payload.get('page_size', 0))}"
                  ).encode())
    if payload.get("layout", "canonical") != "canonical":
        # tp resharding boundary (ISSUE 20): the hash is defined over
        # the CANONICAL host-order bytes — exporters gather their mesh
        # before building the payload, so "canonical" (the only layout
        # this protocol ships) folds nothing in and every existing
        # digest is unchanged. A non-canonical stamp is hashed so it
        # cannot be stripped in flight to sneak mesh-local bytes past
        # the importer's layout check.
        h.update(f"layout={payload['layout']}".encode())
    return h.hexdigest()


def build_payload(*, k: np.ndarray, v: np.ndarray, prompt: np.ndarray,
                  pos: int, first: int, rng: np.ndarray, seed: int,
                  max_new: int, ks: Optional[np.ndarray] = None,
                  vs: Optional[np.ndarray] = None,
                  kv_dtype: Optional[str] = None,
                  page_size: Optional[int] = None,
                  layout: Optional[str] = None) -> Dict[str, Any]:
    """Assemble one ship buffer: the slot's K/V trimmed to ``pos``
    (``[L, pos, H, hd]``, contiguous), the first sampled token, the
    post-prefill PRNG lane, and the replay identity (prompt, seed,
    max_new) — everything a decode engine needs to continue the stream
    bit-exactly, and everything a survivor needs to re-prefill it from
    scratch if the bytes are lost. int8 exporters (ISSUE 16) pass the
    codes as ``k``/``v`` plus the per-page scales ``ks``/``vs``
    (``[L, n_cover, H]``) and the layout identity; the digest then
    covers codes AND scales."""
    payload = {
        "k": np.ascontiguousarray(k),
        "v": np.ascontiguousarray(v),
        "prompt": np.ascontiguousarray(np.asarray(prompt, np.int32)),
        "pos": int(pos),
        "first": int(first),
        "rng": np.ascontiguousarray(np.asarray(rng, np.uint32)),
        "seed": int(seed),
        "max_new": int(max_new),
    }
    if ks is not None:
        payload["ks"] = np.ascontiguousarray(np.asarray(ks, np.float32))
        payload["vs"] = np.ascontiguousarray(np.asarray(vs, np.float32))
        payload["kv_dtype"] = str(kv_dtype or "int8")
        payload["page_size"] = int(page_size or 0)
    if layout is not None and layout != "canonical":
        # Only a NON-canonical stamp is recorded (and digest-folded):
        # canonical is the protocol default, so tp-aware exporters —
        # which always gather to host order first — emit payloads
        # byte-identical to the single-chip plane.
        payload["layout"] = str(layout)
    payload["digest"] = payload_digest(payload)
    return payload


def verify_payload(payload: Dict[str, Any]) -> None:
    """Byte-verify a resolved payload against its stamped digest."""
    want = payload.get("digest")
    if not want:
        raise HandoffError("handoff payload carries no digest")
    got = payload_digest(payload)
    if got != want:
        raise HandoffError(
            f"handoff payload failed byte verification "
            f"(digest {got[:12]} != shipped {want[:12]})")


def payload_nbytes(payload: Dict[str, Any]) -> int:
    n = int(payload["k"].nbytes) + int(payload["v"].nbytes)
    if payload.get("ks") is not None:
        n += int(payload["ks"].nbytes) + int(payload["vs"].nbytes)
    return n


def ship_payload(payload: Dict[str, Any]) -> Tuple[Dict[str, Any], int]:
    """Turn a payload into its wire descriptor half: inline for small
    payloads, an object-plane ref (``rt.put`` → chunked shm pull on the
    consumer) past :data:`SHIP_INLINE_MAX`. Returns ``(fields, nbytes)``
    where ``fields`` carries exactly one of ``payload``/``ref`` — the
    caller merges lease and routing fields on top. Outside a running
    runtime (in-process engine tests) the payload always ships inline.
    """
    nbytes = payload_nbytes(payload)
    core = None
    try:
        from ..core.worker import CoreWorker

        core = CoreWorker._current
    except Exception:  # noqa: BLE001 - no runtime in this process
        core = None
    if core is None or nbytes <= SHIP_INLINE_MAX:
        return {"payload": payload}, nbytes
    from .. import api as rt

    return {"ref": rt.put(payload)}, nbytes


def resolve_payload(desc: Dict[str, Any],
                    timeout_s: float = 30.0) -> Dict[str, Any]:
    """Materialize a descriptor's payload: inline copy, or a pull of
    the shipped object through the chunked-transfer path. Raises
    :class:`HandoffError` when the object is gone — a reclaimed lease
    or a shipper that died mid-transfer — so the caller falls back to a
    local re-prefill."""
    if "payload" in desc:
        return desc["payload"]
    ref = desc.get("ref")
    if ref is None:
        raise HandoffError("handoff descriptor has neither payload nor ref")
    from .. import api as rt

    try:
        return rt.get(ref, timeout=timeout_s)
    except Exception as e:  # noqa: BLE001 - owner died / lease reclaimed
        raise HandoffError(
            f"shipped KV payload unavailable ({type(e).__name__}: {e}); "
            f"lease expired or the prefill replica died mid-ship") from e


class HandoffLease:
    """One granted handoff: the pin keeping the shipped payload alive
    (an ObjectRef, or None for inline ships), its epoch stamp, and its
    expiry on the lease clock."""

    __slots__ = ("lease_id", "epoch", "expires_at", "pin", "nbytes")

    def __init__(self, lease_id: str, epoch: int, expires_at: float,
                 pin: Any, nbytes: int):
        self.lease_id = lease_id
        self.epoch = epoch
        self.expires_at = expires_at
        self.pin = pin
        self.nbytes = nbytes


class LeaseTable:
    """Epoch-stamped lease bookkeeping for shipped KV payloads.

    The prefill engine grants a lease per handoff and holds the only
    pin on the shipped object; :meth:`claim` (the decode side imported
    successfully) and :meth:`sweep` (lease expired unclaimed — the
    decode replica or the router died between grant and claim) both
    drop the pin, each exactly once. Accessed from the engine driver
    thread (grant at export, sweep in the loop) AND replica RPC threads
    (claim), so every mutation runs under ``_lock``.
    """

    def __init__(self, ttl_s: float = 30.0):
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        self._leases: Dict[str, HandoffLease] = {}
        self._counter = 0
        self.granted = 0
        self.claimed = 0
        self.reclaimed = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._leases)

    def grant(self, *, epoch: int, pin: Any = None, nbytes: int = 0,
              ttl_s: Optional[float] = None) -> Tuple[str, float]:
        """Grant one lease; returns ``(lease_id, expires_at)``. The pin
        (if any) is dropped on claim or sweep, never by the caller."""
        ttl = self.ttl_s if ttl_s is None else float(ttl_s)
        with self._lock:
            self._counter += 1
            lease_id = f"ho-{self._counter}-{epoch}"
            expires = time.monotonic() + ttl
            self._leases[lease_id] = HandoffLease(
                lease_id, int(epoch), expires, pin, int(nbytes))
            self.granted += 1
        return lease_id, expires

    def claim(self, lease_id: str, epoch: int) -> bool:
        """Release a lease after a successful import. False when the
        lease is unknown (already swept — the payload may be gone, but
        the importer that claims late already HAS the bytes) or the
        epoch does not match (a stale claim from before a restart must
        not release a newer grant that reused the id space)."""
        with self._lock:
            lease = self._leases.get(lease_id)
            if lease is None or lease.epoch != int(epoch):
                return False
            del self._leases[lease_id]
            self.claimed += 1
            lease.pin = None       # drop the pin: the owner may free
            return True

    def _expired_locked(self, now: float) -> list:  # rtlint: holds=_lock
        """Lease ids past expiry at ``now``. Both call sites (sweep;
        tests poking the clock) hold ``_lock`` — the scan and the pop
        must see one consistent table."""
        return [lid for lid, lease in self._leases.items()
                if lease.expires_at <= now]

    def sweep(self, now: Optional[float] = None) -> int:
        """Reclaim every expired lease, dropping its pin so the object
        plane frees the orphaned ship buffer. Returns the reclaim
        count. Run from the prefill engine's driver loop — the lease
        clock that guarantees a crashed consumer can never pin the
        pool."""
        now = time.monotonic() if now is None else now
        with self._lock:
            expired = self._expired_locked(now)
            for lid in expired:
                lease = self._leases.pop(lid)
                lease.pin = None   # drop the pin: the owner may free
            self.reclaimed += len(expired)
        return len(expired)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"granted": self.granted, "claimed": self.claimed,
                    "reclaimed": self.reclaimed,
                    "outstanding": len(self._leases)}

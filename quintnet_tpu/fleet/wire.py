"""Versioned wire serialization + length-prefixed framing for the
cross-process fleet.

Everything that crosses a process boundary in ``fleet/proc.py`` (and
anything a future remote dispatcher would persist) goes through here:

- **payloads** — ``progress_to_wire``/``progress_from_wire`` for
  :class:`~quintnet_tpu.serve.scheduler.RequestProgress` (THE migration
  contract: prompt + committed tokens + evolved PRNG key + adapter
  binding + remaining deadline), ``request_to_wire``/``request_from_wire``
  for :class:`~quintnet_tpu.serve.scheduler.Request` submit payloads,
  and ``error_to_wire``/``error_from_wire`` for the typed rejection
  types (:class:`~quintnet_tpu.fleet.admission.Overloaded`,
  :class:`~quintnet_tpu.serve.scheduler.DeadlineExceeded`, plus plain
  ``ValueError``/``KeyError`` request-scoped rejections). Every payload
  carries ``{"kind": ..., "v": N}``; a payload whose version this
  build does not speak is rejected with an actionable
  :class:`WireVersionError` naming both versions — never a KeyError
  three fields deep.
- **KV-block frames** — ``kv_chain_to_wire``/``kv_chain_from_wire``
  for the disaggregated fleet's prefill→decode handoff: a published
  prefix chain exported from one replica's :class:`KVPool` (int8
  blocks + per-block scales when the pool is quantized — PR 10's
  layout makes the transfer ~4x smaller at equal positions) framed
  with a **per-frame CRC32 over the canonical payload** so a
  corrupted or truncated transfer is detected at the importer as a
  typed :class:`WireError`, never silently admitted as wrong KV. The
  chain is pure CACHE: an importer that rejects (or never receives)
  the frame falls back to local re-prefill — slower, never wrong —
  which is what makes checksum-reject a safe answer.
- **framing** — ``send_frame``/``recv_frame``: 4-byte big-endian
  length prefix + UTF-8 JSON over any stream socket. JSON, not pickle:
  a replica process must never be able to execute code in the
  dispatcher by crafting a payload, and the frames stay inspectable
  with tcpdump. Arrays ride as base64 raw bytes + dtype + shape, so a
  PRNG key round-trips bit-exactly (a float/list round-trip would not
  be bit-exact for every dtype and the resume contract IS bit-exactness).
  ``recv_frame(..., peer=...)`` names the counterparty in every
  framing error — a dispatcher watching three pools of replicas must
  know WHICH socket desynchronized without correlating stack traces.

The committed-tokens-only discipline of ``RequestProgress``
(speculative drafts never reach an export, serve/scheduler.py) is what
makes this wire format complete: there is no engine-internal state —
spec drafts, prefix-cache chains, tentative blocks — that needs to
cross the wire for a resume to be token-identical. The restoring
engine rebuilds all of it from ``prompt + generated + key_data``.
"""

from __future__ import annotations

import base64
import json
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

# Bump when a payload's schema changes shape. Readers accept exactly
# the versions they know how to decode; unknown versions fail with an
# actionable error instead of silently mis-parsing.
WIRE_VERSION = 1

_LEN = struct.Struct(">I")
MAX_FRAME_BYTES = 64 * 1024 * 1024  # a corrupt length prefix must not
#                                     allocate gigabytes


class WireError(ValueError):
    """Malformed wire payload (bad kind, missing field, bad frame)."""


class WireVersionError(WireError):
    """Payload version this build does not speak."""


class ConnectionClosed(ConnectionError):
    """The peer closed the stream mid-protocol (or before a frame)."""


# ---------------------------------------------------------------------------
# primitives


def _enc_array(a: Optional[np.ndarray]) -> Optional[Dict]:
    if a is None:
        return None
    a = np.ascontiguousarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "b64": base64.b64encode(a.tobytes()).decode("ascii")}


def _dec_array(d: Optional[Dict]) -> Optional[np.ndarray]:
    if d is None:
        return None
    try:
        raw = base64.b64decode(d["b64"])
        a = np.frombuffer(raw, dtype=np.dtype(d["dtype"]))
        return a.reshape(d["shape"]).copy()
    except (KeyError, TypeError, ValueError) as e:
        raise WireError(f"malformed array payload {d!r}: {e}") from e


def _check_header(payload: Dict, kind: str,
                  known_versions: Tuple[int, ...] = (WIRE_VERSION,)):
    if not isinstance(payload, dict):
        raise WireError(
            f"expected a {kind!r} payload dict, got {type(payload).__name__}")
    got_kind = payload.get("kind")
    if got_kind != kind:
        raise WireError(
            f"expected payload kind {kind!r}, got {got_kind!r} — the "
            f"frame was routed to the wrong decoder")
    v = payload.get("v")
    if v not in known_versions:
        raise WireVersionError(
            f"{kind} payload version {v!r} is not supported by this "
            f"build (speaks {list(known_versions)}); upgrade the older "
            f"side of the connection — dispatcher and replicas must "
            f"deserialize each other's payloads")


def _require(payload: Dict, kind: str, *fields: str):
    missing = [f for f in fields if f not in payload]
    if missing:
        raise WireError(
            f"{kind} payload (v{payload.get('v')}) is missing required "
            f"field(s) {missing}: {sorted(payload)} present")


# ---------------------------------------------------------------------------
# RequestProgress — the migration contract


def progress_to_wire(p) -> Dict:
    """Serialize a :class:`RequestProgress` (committed tokens only —
    see the class docstring for why that is complete)."""
    return {
        "kind": "request_progress",
        "v": WIRE_VERSION,
        "rid": int(p.rid),
        "prompt": _enc_array(np.asarray(p.prompt, np.int32)),
        "generated": [int(t) for t in p.generated],
        "key_data": _enc_array(None if p.key_data is None
                               else np.asarray(p.key_data)),
        "max_new_tokens": int(p.max_new_tokens),
        "priority": int(p.priority),
        "preemptions": int(p.preemptions),
        "adapter_id": p.adapter_id,
        "deadline_s": (None if p.deadline_s is None
                       else float(p.deadline_s)),
        "prefilled": int(p.prefilled),
        # observability identity (quintnet_tpu/obs/): carried so the
        # destination replica's spans continue the source's timeline.
        # Optional and inert — absent on pre-obs payloads, never
        # touches the resume math — so WIRE_VERSION stays unchanged.
        "trace_id": p.trace_id,
    }


def progress_from_wire(payload: Dict):
    from quintnet_tpu.serve.scheduler import RequestProgress

    _check_header(payload, "request_progress")
    _require(payload, "request_progress", "rid", "prompt", "generated",
             "key_data", "max_new_tokens")
    return RequestProgress(
        rid=int(payload["rid"]),
        prompt=_dec_array(payload["prompt"]),
        generated=[int(t) for t in payload["generated"]],
        key_data=_dec_array(payload["key_data"]),
        max_new_tokens=int(payload["max_new_tokens"]),
        priority=int(payload.get("priority", 0)),
        preemptions=int(payload.get("preemptions", 0)),
        adapter_id=payload.get("adapter_id"),
        deadline_s=payload.get("deadline_s"),
        # chunked-prefill high-water mark (serve/longctx.py) —
        # informational; absent on pre-longctx payloads
        prefilled=int(payload.get("prefilled", 0)),
        trace_id=payload.get("trace_id"))


# ---------------------------------------------------------------------------
# Request — the submit payload


def request_to_wire(req, *, deadline_s: Optional[float] = None) -> Dict:
    """Serialize a :class:`~quintnet_tpu.serve.scheduler.Request`
    submit payload (the callback and engine-runtime fields stay local;
    ``deadline_s`` is the REMAINING budget — absolute clock times do
    not survive a process boundary)."""
    return {
        "kind": "request",
        "v": WIRE_VERSION,
        "rid": int(req.rid),
        "prompt": _enc_array(np.asarray(req.prompt, np.int32)),
        "max_new_tokens": int(req.max_new_tokens),
        "priority": int(req.priority),
        "key_data": _enc_array(None if req.key_data is None
                               else np.asarray(req.key_data)),
        "generated": [int(t) for t in req.generated],
        "adapter_id": req.adapter_id,
        "deadline_s": None if deadline_s is None else float(deadline_s),
    }


def request_from_wire(payload: Dict):
    from quintnet_tpu.serve.scheduler import Request

    _check_header(payload, "request")
    _require(payload, "request", "rid", "prompt", "max_new_tokens")
    req = Request(
        rid=int(payload["rid"]),
        prompt=_dec_array(payload["prompt"]),
        max_new_tokens=int(payload["max_new_tokens"]),
        priority=int(payload.get("priority", 0)),
        adapter_id=payload.get("adapter_id"))
    req.key_data = _dec_array(payload.get("key_data"))
    req.generated = [int(t) for t in payload.get("generated", [])]
    return req, payload.get("deadline_s")


# ---------------------------------------------------------------------------
# KV-block chain — the disaggregated prefill→decode handoff payload


# geometry fields a KV frame must agree on with the importing pool —
# a mismatch is a deployment error (mixed engine specs in one fleet),
# surfaced as a typed WireError at import, never a shape crash inside
# a jitted program
KV_GEOMETRY_FIELDS = ("policy", "block_size", "n_layers", "n_kv_heads",
                      "head_dim")


def kv_chain_checksum(payload: Dict,
                      _decoded: Optional[List] = None,
                      _raw: Optional[List[Dict]] = None) -> int:
    """CRC32 over the frame's header (canonical JSON, minus the
    checksum and the block list) chained with every block's RAW bytes
    — dtype/shape descriptors and decoded array data, not their
    base64/JSON spelling. Hashing the raw bytes keeps the checksum
    O(chain bytes) with no re-serialization of megabyte payloads (the
    decode replica verifies this between decode steps), while still
    catching any flip in geometry, fill counts, array metadata or
    payload bits. Two internal hooks keep each hot path to ONE pass
    over the chain bytes: ``_decoded`` (:func:`kv_chain_from_wire`)
    collects each block's arrays as they are base64-decoded for
    hashing, and ``_raw`` (:func:`kv_chain_to_wire`) supplies the
    per-block raw bytes the encoder just serialized so the export
    side never base64-decodes what it just encoded."""
    head = {k: v for k, v in payload.items()
            if k not in ("crc32", "blocks")}
    crc = zlib.crc32(json.dumps(head, sort_keys=True,
                                separators=(",", ":")).encode("utf-8"))
    for i, b in enumerate(payload.get("blocks", ())):
        if not isinstance(b, dict):
            raise WireError(
                f"kv_chain block entry is {type(b).__name__}, "
                f"expected a dict — cannot checksum the frame")
        try:
            fill = int(b.get("fill", -1))
        except (TypeError, ValueError) as e:
            # null / non-numeric fill from a buggy or corrupted peer:
            # a TYPED error the import handler maps to a failed
            # transfer — never a TypeError that escapes replica_main
            # and reads as a replica death
            raise WireError(
                f"kv_chain block field 'fill' is malformed ({e}) — "
                f"cannot checksum the frame") from e
        crc = zlib.crc32(str(fill).encode("ascii"), crc)
        rec = {"fill": fill} if _decoded is not None else None
        raws = _raw[i] if _raw is not None else None
        for key in ("k", "v", "k_scale", "v_scale"):
            d = b.get(key)
            if d is None:
                crc = zlib.crc32(b"\x00none", crc)
                if rec is not None:
                    rec[key] = None
                continue
            try:
                meta = json.dumps({"dtype": d["dtype"],
                                   "shape": d["shape"]},
                                  sort_keys=True,
                                  separators=(",", ":"))
                raw = (raws[key] if raws is not None
                       else base64.b64decode(d["b64"]))
                if rec is not None:
                    rec[key] = np.frombuffer(
                        raw, dtype=np.dtype(d["dtype"])).reshape(
                            d["shape"]).copy()
            except (KeyError, TypeError, ValueError) as e:
                raise WireError(
                    f"kv_chain block field {key!r} is malformed "
                    f"({e}) — cannot checksum the frame") from e
            crc = zlib.crc32(meta.encode("utf-8"), crc)
            crc = zlib.crc32(raw, crc)
        if rec is not None:
            _decoded.append(rec)
    return crc & 0xFFFFFFFF


def kv_chain_wire_size(payload: Dict) -> int:
    """Conservative OVER-estimate of the framed byte size of a
    KV-chain payload without serializing it (the b64 strings dominate;
    keys, digits and punctuation ride in the per-field slack)."""
    size = 4096
    tokens = payload.get("tokens")
    if isinstance(tokens, dict):
        size += len(tokens.get("b64", "")) + 256
    for b in payload.get("blocks", ()):
        size += 512
        for key in ("k", "v", "k_scale", "v_scale"):
            d = b.get(key)
            if isinstance(d, dict):
                size += len(d.get("b64", "")) + 256
    return size


def kv_chain_fits(payload: Dict) -> bool:
    """Would this KV-chain frame fit under :data:`MAX_FRAME_BYTES`?
    The EXPORTER must check before shipping: an oversized frame would
    trip the receiver's length guard, which reads as a desynchronized
    stream and kills the CONNECTION — turning a healthy replica into
    a declared death. Declining the transfer instead lets the handoff
    take its documented fallback (local re-prefill on the decode
    side: slower, never wrong)."""
    return kv_chain_wire_size(payload) <= MAX_FRAME_BYTES


def kv_chain_to_wire(chain: Dict, *,
                     namespace: Optional[str] = None) -> Dict:
    """Serialize one exported prefix chain
    (:meth:`~quintnet_tpu.serve.kv_pool.KVPool.export_chain`): the
    covered token prefix, the pool geometry the blocks were laid out
    under, and each block's slot data (+ per-block-per-head scales for
    scaled policies) as raw bytes — int8 blocks ship as int8, which is
    what makes a quantized handoff ~4x smaller than f32. The frame
    carries a CRC32 so the importer can refuse a corrupted transfer
    with a typed error instead of caching wrong KV."""
    def enc(a):
        """(encoded dict, raw bytes): the same bytes the b64 field
        spells, kept so the checksum hashes them directly instead of
        base64-decoding what this function just encoded."""
        if a is None:
            return None, None
        a = np.ascontiguousarray(a)
        raw = a.tobytes()
        return {"dtype": str(a.dtype), "shape": list(a.shape),
                "b64": base64.b64encode(raw).decode("ascii")}, raw

    blocks, raw_blocks = [], []
    for b in chain["blocks"]:
        rec, raws = {"fill": int(b["fill"])}, {}
        for key in ("k", "v", "k_scale", "v_scale"):
            # k is mandatory in an exported chain; v is absent from a
            # latent pool's records, scales only exist under scaled
            # layout policies
            a = b[key] if key == "k" else b.get(key)
            rec[key], raws[key] = enc(a)
        blocks.append(rec)
        raw_blocks.append(raws)
    payload = {
        "kind": "kv_chain",
        "v": WIRE_VERSION,
        "namespace": namespace,
        "n_tokens": int(chain["n_tokens"]),
        "tokens": _enc_array(np.asarray(chain["tokens"], np.int32)),
        "policy": str(chain["policy"]),
        "block_size": int(chain["block_size"]),
        "n_layers": int(chain["n_layers"]),
        "n_kv_heads": int(chain["n_kv_heads"]),
        "head_dim": int(chain["head_dim"]),
        "blocks": blocks,
    }
    payload["crc32"] = kv_chain_checksum(payload, _raw=raw_blocks)
    return payload


def kv_chain_from_wire(payload: Dict) -> Tuple[Dict, Optional[str]]:
    """Decode + VERIFY one KV-chain frame; returns ``(chain,
    namespace)`` in :meth:`KVPool.import_chain` shape. A checksum
    mismatch — a flipped bit, a truncated block, any corruption the
    transport let through — is a typed :class:`WireError`: the
    importer discards the frame and the handoff either retries or
    falls back to local re-prefill (correct because the chain is just
    cache). Never raises a raw ``KeyError``/``struct.error``."""
    _check_header(payload, "kv_chain")
    _require(payload, "kv_chain", "crc32", "tokens", "n_tokens",
             "blocks", *KV_GEOMETRY_FIELDS)
    if not isinstance(payload["blocks"], list) or not payload["blocks"]:
        raise WireError("kv_chain payload carries no blocks")
    for b in payload["blocks"]:
        if not isinstance(b, dict):
            raise WireError(
                f"kv_chain block entry is {type(b).__name__}, "
                f"expected a dict")
        _require(b, "kv_chain block", "fill", "k", "v")
    want = payload["crc32"]
    # the checksum walk base64-decodes every array to hash its raw
    # bytes; collect them as it goes so the hot path (decode replica,
    # between decode steps) never decodes a megabyte chain twice
    blocks: List = []
    got = kv_chain_checksum(payload, _decoded=blocks)
    if got != want:
        raise WireError(
            f"kv_chain checksum mismatch (frame says {want:#010x}, "
            f"payload hashes to {got:#010x}) — the KV transfer was "
            f"corrupted in flight; discarding the frame (the handoff "
            f"retries or the decode replica re-prefills locally)")
    try:
        chain = {
            "n_tokens": int(payload["n_tokens"]),
            "tokens": _dec_array(payload["tokens"]),
            "policy": payload["policy"],
            "block_size": int(payload["block_size"]),
            "n_layers": int(payload["n_layers"]),
            "n_kv_heads": int(payload["n_kv_heads"]),
            "head_dim": int(payload["head_dim"]),
            "blocks": blocks,
        }
    except WireError:
        raise               # _dec_array already typed it precisely
    except (TypeError, ValueError) as e:
        # null / non-numeric geometry from a buggy peer checksums
        # consistently (the peer hashed the same nulls), so it reaches
        # here — surface it typed, never a TypeError that escapes the
        # import handler and reads as a replica death
        raise WireError(
            f"kv_chain geometry field is malformed ({e}); "
            f"discarding the frame") from e
    return chain, payload.get("namespace")


# ---------------------------------------------------------------------------
# typed errors (shed / deadline / request-scoped rejections)


def error_to_wire(e: BaseException) -> Dict:
    from quintnet_tpu.fleet.admission import Overloaded
    from quintnet_tpu.serve.scheduler import DeadlineExceeded

    out = {"kind": "error", "v": WIRE_VERSION, "message": str(e)}
    if isinstance(e, Overloaded):
        out["type"] = "overloaded"
        out["reason"] = e.reason
    elif isinstance(e, DeadlineExceeded):
        out["type"] = "deadline_exceeded"
        out["rid"] = getattr(e, "rid", None)
        out["generated"] = getattr(e, "generated", 0)
    elif isinstance(e, WireError):
        # distinct from a plain ValueError ON PURPOSE: a WireError is
        # a damaged/mis-framed payload — TRANSIENT, the handoff retry
        # loop re-exports — while a plain ValueError (geometry
        # mismatch, evicted chain) is permanent and goes straight to
        # the fallback
        out["type"] = "wire_error"
    elif isinstance(e, KeyError):
        out["type"] = "key_error"
    else:
        # ValueError and anything else request-scoped: the receiving
        # side re-raises a ValueError with the original message — the
        # TYPE of an arbitrary exception does not cross the wire
        out["type"] = "value_error"
    return out


def error_from_wire(payload: Dict) -> BaseException:
    from quintnet_tpu.fleet.admission import Overloaded
    from quintnet_tpu.serve.scheduler import DeadlineExceeded

    _check_header(payload, "error")
    _require(payload, "error", "type", "message")
    t, msg = payload["type"], payload["message"]
    if t == "overloaded":
        return Overloaded(payload.get("reason", "shutdown"), msg)
    if t == "deadline_exceeded":
        return DeadlineExceeded(msg, rid=payload.get("rid"),
                                generated=int(payload.get("generated", 0)))
    if t == "wire_error":
        return WireError(msg)
    if t == "key_error":
        return KeyError(msg)
    return ValueError(msg)


# ---------------------------------------------------------------------------
# framing


def send_frame(sock, obj: Dict) -> None:
    """One length-prefixed JSON frame. The caller serializes access —
    two threads interleaving sendall() would corrupt the stream."""
    data = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    sock.sendall(_LEN.pack(len(data)) + data)


def _peer_name(peer: Optional[str]) -> str:
    return repr(peer) if peer else "peer"


def _recv_exact(sock, n: int, *, peer: Optional[str] = None) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionClosed(
                f"{_peer_name(peer)} closed the connection mid-frame "
                f"({len(buf)}/{n} bytes received)")
        buf.extend(chunk)
    return bytes(buf)


def recv_frame(sock, *, peer: Optional[str] = None) -> Dict:
    """Blocking read of one frame; raises :class:`ConnectionClosed` on
    EOF (a SIGKILL'd peer looks like EOF after the kernel flushes
    whatever it had buffered — the dispatcher drains those frames
    first, which is what keeps the token journal complete). ``peer``
    names the counterparty in every error — a truncated frame, a
    corrupt length prefix or non-JSON bytes all surface as typed
    :class:`ConnectionClosed`/:class:`WireError` naming WHO
    desynchronized, never a raw ``struct.error`` (``_LEN.unpack``
    only ever sees exactly 4 bytes) or a bare ``JSONDecodeError``."""
    head = sock.recv(_LEN.size)
    if not head:
        raise ConnectionClosed(
            f"{_peer_name(peer)} closed the connection")
    if len(head) < _LEN.size:
        head += _recv_exact(sock, _LEN.size - len(head), peer=peer)
    (n,) = _LEN.unpack(head)
    if n > MAX_FRAME_BYTES:
        raise WireError(
            f"frame length {n} from {_peer_name(peer)} exceeds "
            f"MAX_FRAME_BYTES ({MAX_FRAME_BYTES}) — corrupt length "
            f"prefix or a desynchronized stream")
    try:
        return json.loads(_recv_exact(sock, n, peer=peer)
                          .decode("utf-8"))
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise WireError(
            f"frame from {_peer_name(peer)} is not valid JSON "
            f"(flipped bits or a desynchronized stream): {e}") from e

"""Host-RAM second tier under the paged KV pool's prefix cache.

The device pool's prefix index (serve/kv_pool.py) retains refcount-zero
published chains until allocation pressure evicts them — and eviction
used to DESTROY the chain: every future request for that prefix paid a
full re-prefill. At fleet scale the shared-prefix working set (system
prompts x tenants x conversations) vastly exceeds device HBM, so the
hot tail of the LRU is exactly the traffic that keeps getting
re-prefilled.

This module adds the missing tier: when :meth:`KVPool._evict_lru`
would destroy a published block, the pool DEMOTES it here instead — a
host copy of the block's slot data exactly as stored (the layout
policy's ``store_dtype``, so int8 pools demote ~4x smaller records,
plus the per-block-per-head scale rows when scaled: byte-identical to
one record of :meth:`KVPool.export_chain`). Records are keyed by the
block's prefix-index key bytes — the NUL-terminated namespace prefix +
literal token bytes — so host lookups walk the same key ladder device
lookups do and adapter namespaces stay isolated across tiers for free.

Admission then has a THIRD outcome beyond device-hit / miss: a
**host-hit** (the combined device+host walk covers more than the
device chain alone). A host-hit re-promotes the chain through the
pool's existing fused ``import_chain`` scatter instead of
re-prefilling — and promotion is asynchronous: the engine parks the
request in a ``PROMOTING`` state (serve/scheduler.py) and keeps
decoding every other slot while at most a per-step block budget of
host->device copies lands each step (the Sarathi budget discipline
from chunked prefill, applied to memcpy instead of prefill compute).

The tier is BOUNDED: ``byte_budget`` caps resident record bytes with
the tier's own LRU (least-recently demoted/probed records drop first),
so demotion can never grow host memory without limit — and a record
evicted here is simply a miss, never an error: the tier is cache under
cache, and every degraded path falls back to re-prefill, which is
always token-correct.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def record_nbytes(rec: Dict) -> int:
    """Host bytes one demoted block record holds (slot data + scale
    rows). The ledger the byte budget is enforced against."""
    n = rec["k"].nbytes
    if rec.get("v") is not None:     # a latent pool's records have none
        n += rec["v"].nbytes
    if "k_scale" in rec:
        n += rec["k_scale"].nbytes + rec["v_scale"].nbytes
    return n


class HostTier:
    """Bounded host-RAM store of demoted KV blocks, LRU-evicted.

    One record per demoted block, in the ``export_chain`` per-block
    format (``{"fill", "k", "v"[, "k_scale", "v_scale"]}``), keyed by
    the block's prefix-index key bytes. The tier is INCLUSIVE: a
    promoted record stays resident, so a later re-demotion of the same
    (byte-identical) block is a cheap overwrite, not a loss.

    Single-threaded like the pool that owns it (all mutation happens
    on the engine's step thread); counters are plain ints.
    """

    def __init__(self, *, byte_budget: int):
        if byte_budget <= 0:
            raise ValueError(
                f"byte_budget must be > 0, got {byte_budget} "
                f"(a tier that can hold nothing is prefix_cache-only "
                f"— build the pool without a host tier instead)")
        self.byte_budget = int(byte_budget)
        self.bytes_used = 0
        # ordered oldest -> newest: OrderedDict IS the tier's LRU
        # (move_to_end on every hit, popitem(last=False) to evict)
        self._records: "OrderedDict[bytes, Dict]" = OrderedDict()
        # monotone counters, surfaced through ServeMetrics.summary()
        self.demotions = 0         # blocks demoted in (puts)
        self.promotions = 0        # blocks promoted back to device
        self.promoted_tokens = 0   # token positions those blocks held
        self.evictions = 0         # records dropped for the budget

    def __len__(self) -> int:
        return len(self._records)

    def contains(self, key: bytes) -> bool:
        """Membership WITHOUT an LRU touch — the probe used by chain
        walks (a walk must not rejuvenate records it never moves)."""
        return key in self._records

    def get(self, key: bytes) -> Optional[Dict]:
        """The record for ``key`` (LRU-touched), or None."""
        rec = self._records.get(key)
        if rec is not None:
            self._records.move_to_end(key)
        return rec

    def put(self, key: bytes, rec: Dict) -> bool:
        """Demote one block record. Evicts least-recently-used records
        until the budget holds; a record larger than the whole budget
        is refused (False) rather than flushing the tier for a block
        that can never be retained."""
        nbytes = record_nbytes(rec)
        if nbytes > self.byte_budget:
            return False
        old = self._records.pop(key, None)
        if old is not None:
            self.bytes_used -= record_nbytes(old)
        while self.bytes_used + nbytes > self.byte_budget:
            _k, dropped = self._records.popitem(last=False)
            self.bytes_used -= record_nbytes(dropped)
            self.evictions += 1
        self._records[key] = rec
        self.bytes_used += nbytes
        self.demotions += 1
        return True

    def summary(self) -> Dict:
        """JSON-able tier counters (the engine folds these into
        ``ServeMetrics.summary()`` each step)."""
        return {"records": len(self._records),
                "bytes_used": self.bytes_used,
                "byte_budget": self.byte_budget,
                "demotions": self.demotions,
                "promotions": self.promotions,
                "promoted_tokens": self.promoted_tokens,
                "evictions": self.evictions}


@dataclass
class PromotionState:
    """Host-side progress of one request's asynchronous host->device
    promotion (the ChunkState idiom from serve/longctx.py applied to
    memcpy): the request sits at the head of the waiting queue in the
    ``PROMOTING`` state while the engine feeds at most its per-step
    block budget of promotions each step; when ``next`` reaches the
    end of ``keys`` (or the chain truncates — a host record evicted
    mid-flight), the request returns to ``WAITING`` and the normal
    admission path finds the promoted chain as an ordinary device
    prefix hit. Every early exit is therefore correct by construction:
    whatever landed is cache, whatever did not is re-prefilled."""

    req: object                        # the owning scheduler Request
    keys: List[bytes] = field(default_factory=list)
    next: int = 0                      # keys[:next] already consumed

    @property
    def done(self) -> bool:
        return self.next >= len(self.keys)

    @property
    def remaining(self) -> int:
        return len(self.keys) - self.next

"""Paged KV-cache pool: refcounted blocks + prefix cache + free list.

The dense decoders allocate [L, B, H, T_max, Dh] per batch — every
request pays for the longest possible sequence. Here KV memory is a
single pool of ``num_blocks`` blocks of ``block_size`` token slots,
shared by all in-flight requests; each request owns just the blocks its
current length needs (vLLM's PagedAttention memory model). Fragmentation
is bounded to < 1 block per request and T_max padding disappears.

Device layout (per k and v): ``[L, num_blocks * block_size, F]`` — one
ROW per token slot, its heads flattened (``H_kv * Dh`` features) and
padded with zero lanes to a multiple of 128 (:func:`feature_width`).
The shape is the layout: a TPU gives a parameter the tiling that pads
least for its shape, and for trailing dims ``25, 64`` (or ``1600``)
that tiling has the SLOT dim minor — a token's row strided, re-laid by
every program that touches it. With the minor dim a whole number of
lanes the default is row-major, nothing strided (PERF.md, PR 28).
Programs carry the pool WHOLE through their layer loop and address it
by ``(layer, slot)`` (nn/attention.paged_write scatters rows in,
paged_gather pages a row's blocks out, and only a program with many
query rows then splits them into heads);
the flat "slot" dim is ``block * block_size + offset``. Under TP the
feature dim is head-sharded over the mesh: each rank holds its LOCAL
heads' rows, padded on their own (``F = tp * feature_width(H_kv / tp
* Dh)``, heads contiguous within a rank's part). Pad lanes are ZERO
— every writer keeps them so, because decode multiplies them by zero
query lanes instead of cutting them off (docs/serving.md, "The
pad-lane rule") — and count for nothing: ``bytes_per_token`` is the
model's own. Host-side records (chain export/import, the host tier,
the disaggregated handoff) keep ``[L, block_size, H_kv, Dh]``; the
reshape happens at the pool's edge (:meth:`KVPool.read_slots`,
:meth:`KVPool.write_slots`). WHAT a slot stores is a
:class:`~quintnet_tpu.serve.kv_quant.KVLayoutPolicy`: f32/bf16
passthrough, or int8 with per-block-per-head absmax scales carried in
``[L, num_blocks, H_kv]`` f32 arrays beside the pools (head-sharded
the same way, carried and addressed by layer the same way) — same pool
bytes, ~4x the blocks.

Block 0 is permanently reserved as the NULL block: inactive engine
slots point their table rows (and positions) at it, so masked rows'
scatters land in memory nobody reads and the decode step needs no
dynamic shapes. The allocator therefore hands out blocks [1, num_blocks).

Allocation is host-side bookkeeping — the device arrays never reshape;
"allocating" a block just means an engine slot's block table starts
referencing it.

Recurrent families (state-space layers among attention ones,
serve/families.py ``Family.state``) keep a SECOND kind of per-sequence
memory in the same manager: beside the blocks of their attention
layers, one fixed-size state per engine SLOT for every recurrent layer
— ``ssm [L_r, max_slots + 1, ...]`` f32 and ``conv [L_r, max_slots + 1,
...]`` in the pool's stored dtype (:class:`StateShapes`). Row = slot;
the last row is the null row, the state's counterpart of block 0
(warmup writes there). The buffers ride every program beside the pools
(:meth:`KVPool.caches`) and are updated in place. State has no blocks
to share: the prefix index, the host tier and chain export/import know
nothing of it, and the engine refuses them for such a family.

Window families (sliding-window attention layers among global ones,
``Family.window`` set) keep a THIRD kind: beside the blocks of their
global layers — which hold every position of a sequence, through the
block table — each sliding layer keeps a RING of ``window + block_size``
positions per engine SLOT, ``wk``/``wv`` ``[L_w, (max_slots + 1) * ring,
F]`` in the pool's stored dtype (:class:`WindowShapes`; the last ring is
the null one). Position ``p`` of the sequence in slot ``s`` lives at row
``s * ring + p % ring`` (nn/attention.py, "The WINDOW store"): a
sequence's bytes in those layers are bounded by the window whatever its
length (:attr:`KVPool.window_bytes_per_slot`), a decode step reads
``ring`` rows of it and never the table's width, and nothing is
allocated or freed as it grows. Why a ring a slot and not a second set
of blocks released behind the window: the bound then holds by
construction instead of by a second allocator keeping up, a step ships
no second table, and 528 rows x 2 KB x 2 x 3 layers = 6.5 MB a slot is
1.3% of a chip at 32 slots — less than the blocks a paged window would
keep half empty at its two ends. The pool keeps the ledger of who owns
which ring (:meth:`KVPool.window_acquire` / :meth:`window_release`,
``window_owner``) and counts both kinds at admission
(:class:`AdmitPlan`.window_bytes, :meth:`can_admit`). A ring holds nothing a second sequence could
share: the prefix index, the host tier and chain export/import are
refused for such a family (serve/engine.py).

Latent families (multi-head latent attention, ``Family.latent`` set)
keep ONE row kind: ``[c | k_rope]``, the compressed kv and the shared
rotary key of a token, ``latent`` features padded to whole lane rows
like any other (576 -> 640). The pool is built with ``latent=576``:
``k`` holds the rows, there is NO ``v`` (``pool.v is None``,
``caches()`` is ``(k,)``), ``n_kv_heads`` is 1 and ``head_dim`` the row's
features, so host records are ``[L, n, 1, latent]`` and carry no ``v``.
Blocks, refcounts, the prefix index, chain export/import and the host
tier deal in blocks and records and work unchanged.

Both at once (linear-attention layers with a latent layer among them,
serve/families.ling_hybrid_family): ``KVPool(latent=576,
state=StateShapes(...))`` keeps the one latent buffer for the layers
that cache a row a position and the two per-slot buffers for the layers
that keep a state — ``caches()`` is ``(k, ssm, conv)`` — and what a
recurrent family cannot share or move (the prefix index, the host tier,
chain export/import) stays refused by the engine.

Prefix caching (the PagedAttention sharing model + SGLang-style prefix
reuse, block-granular):

- every block carries a **refcount** — the number of live block tables
  (plus transient admission pins) referencing it; ``acquire``/``release``
  replace grow-only alloc/free with share-aware accounting;
- a **prefix index** maps ``token_ids[:n].tobytes()`` -> the pool block
  holding positions ``[n - fill, n)`` of that exact token chain. Full
  blocks are keyed at block boundaries (``n = (j+1) * block_size``); a
  final partially-filled block is keyed at its exact token count. The
  full-token key (not a hash) makes collisions impossible — a wrong
  match would silently corrupt the golden token-parity contract;
- on retire/preempt the engine **publishes** a request's blocks into
  the index instead of freeing them; a published block whose refcount
  drops to zero is RETAINED in an LRU set rather than pushed onto the
  free list. Allocation consumes the LIFO free list first (warm pages)
  and only then **evicts** the least-recently-touched cached block —
  cached-but-unreferenced memory is free memory that happens to still
  be useful;
- a later request with the same token prefix re-acquires the cached
  chain (refcount back up, table entries cloned) and prefills only the
  uncached tail. When the reusable chain ends inside a partially-filled
  block, the engine **copies-on-write**: the cached block's filled
  slots are copied into a private block before the new request writes
  its own (diverging) continuation — the cached copy is immutable while
  the index references it.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import jax.numpy as jnp

from quintnet_tpu.serve.kv_quant import KVLayoutPolicy, make_policy
from quintnet_tpu.serve.kv_tier import HostTier

NULL_BLOCK = 0

LANES = 128


def feature_width(n_heads: int, head_dim: int) -> int:
    """The pool's row width for ``n_heads`` heads of ``head_dim``:
    their flattened features rounded up to whole 128-lane vregs (GPT-2
    XL's 25 x 64 = 1600 -> 1664; 768, 1280 and 512 need no pad)."""
    return -(-n_heads * head_dim // LANES) * LANES


@dataclass
class AdmitPlan:
    """Host-side admission plan for one request's token sequence.

    ``cached_tokens`` positions are served from the prefix index:
    ``shared_blocks`` are re-referenced whole (read-only, one refcount
    each), and — when the chain ends inside a partially-filled block —
    ``cow_src`` names the cached block whose first ``cow_len`` slots
    must be copied into the request's first private block before its
    tail is written (copy-on-write). ``n_new_blocks`` private blocks
    complete the table."""

    cached_tokens: int                  # prefill starts at this offset
    shared_blocks: List[int] = field(default_factory=list)
    cow_src: Optional[int] = None
    cow_len: int = 0
    n_new_blocks: int = 0
    # a window family's fixed store beside the blocks: the bytes of the
    # ONE ring per sliding layer the admitted slot will own (0 for every
    # other family)
    window_bytes: int = 0

    @property
    def pinned_blocks(self) -> List[int]:
        """Blocks that must be refcount-pinned before any allocation
        (allocation may evict refcount-zero cached blocks — including,
        without the pin, the very chain this plan reuses)."""
        return self.shared_blocks + (
            [self.cow_src] if self.cow_src is not None else [])


@dataclass(frozen=True)
class StateShapes:
    """What ONE slot keeps for ONE recurrent layer beside the paged
    blocks: the f32 state (Mamba-2: heads x head size x state size) and
    the causal conv's tail (kernel - 1 rows of its channels), the
    latter in the pool's stored dtype and FLAT, its rows side by side:
    three rows of 4,352 as the two minor dimensions of a bf16 array are
    padded to sixteen by the TPU's tiling, 5.3 times the bytes in HBM
    and in every decode step's traffic."""

    n_layers: int                       # recurrent layers
    ssm: Tuple[int, ...]
    conv: Tuple[int, ...]


@dataclass(frozen=True)
class WindowShapes:
    """What ONE slot keeps for the sliding-window layers beside the
    paged blocks of the global ones: ``n_layers`` rings of ``ring``
    positions (``window`` + one block: the spare rows are what a run of
    up to ``block_size + 1`` tokens overwrites before it reads —
    nn/attention.window_attend), each position one k and one v pool row."""

    n_layers: int                       # sliding-window layers
    window: int
    ring: int


class KVPool:
    """Refcounted block allocator + prefix cache over paged KV storage.

    ``n_kv_heads`` is the GLOBAL kv-head count; pass ``sharding`` (a
    ``jax.sharding.NamedSharding`` with the feature dim — dim 2 — on
    the tp axis) to lay the pool out head-sharded for a TP engine: the
    axis's size is the number of parts the heads are padded in
    (module docstring). ``prefix_cache=False``
    disables the index entirely (lookup misses, publish is a no-op,
    release always frees).
    """

    def __init__(self, *, n_layers: int, n_kv_heads: int, head_dim: int,
                 block_size: int, num_blocks: int, dtype=jnp.float32,
                 policy: "KVLayoutPolicy | str | None" = None,
                 sharding=None, scale_sharding=None,
                 prefix_cache: bool = True,
                 host_tier: Optional[HostTier] = None,
                 state: Optional[StateShapes] = None,
                 max_slots: int = 0, latent: Optional[int] = None,
                 window: Optional[WindowShapes] = None):
        if latent is not None:
            # one row kind a token (module docstring)
            if n_kv_heads != 1 or head_dim != latent:
                raise ValueError(
                    f"a latent pool holds ONE row of {latent} features a "
                    f"token: pass n_kv_heads=1, head_dim={latent}")
            if sharding is not None:
                raise NotImplementedError(
                    "a latent pool is not head-sharded: all heads read "
                    "the one row")
        self.latent = latent
        if block_size < 1 or num_blocks < 2:
            raise ValueError(
                f"need block_size >= 1 and num_blocks >= 2 (block 0 is "
                f"the reserved null block); got {block_size}, {num_blocks}")
        self.n_layers = n_layers
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prefix_cache = bool(prefix_cache)
        # layout policy (serve/kv_quant.py): ``policy`` wins; the plain
        # ``dtype`` arg (the pre-policy surface) maps to its
        # passthrough policy. Scaled policies additionally allocate one
        # f32 per-block-per-head scale array per pool — under tp the
        # head dim shards exactly like the pool's (``scale_sharding``).
        self.policy: KVLayoutPolicy = make_policy(
            policy if policy is not None else dtype)
        # head shards: the size of the mesh axis the feature dim is on
        axis = sharding.spec[2] if sharding is not None else None
        self.head_shards = 1 if axis is None else sharding.mesh.shape[axis]
        self._local_width = feature_width(n_kv_heads // self.head_shards,
                                          head_dim)
        shape = (n_layers, num_blocks * block_size,
                 self.head_shards * self._local_width)
        k = jnp.zeros(shape, self.policy.store_dtype)
        v = (None if latent is not None
             else jnp.zeros(shape, self.policy.store_dtype))
        k_scale = v_scale = None
        if self.policy.scaled:
            if latent is not None:
                raise NotImplementedError(
                    f"a latent pool under the scaled policy "
                    f"{self.policy.name!r} is not implemented: the row "
                    f"is one group with no per-head scale (ROADMAP M3)")
            k_scale = jnp.ones((n_layers, num_blocks, n_kv_heads),
                               jnp.float32)
            v_scale = jnp.ones((n_layers, num_blocks, n_kv_heads),
                               jnp.float32)
        if sharding is not None:
            import jax

            k = jax.device_put(k, sharding)
            v = jax.device_put(v, sharding)
            if k_scale is not None and scale_sharding is not None:
                k_scale = jax.device_put(k_scale, scale_sharding)
                v_scale = jax.device_put(v_scale, scale_sharding)
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale
        # per-slot recurrent state (module docstring); None = KV only
        self.state = state
        self.ssm = self.conv = None
        self._state_bytes_per_slot = 0
        if state is not None:
            if (self.policy.scaled or sharding is not None
                    or not jnp.issubdtype(self.policy.store_dtype,
                                          jnp.floating)
                    or jnp.dtype(self.policy.store_dtype).itemsize < 2):
                raise NotImplementedError(
                    f"recurrent state beside a {self.policy.name!r} or "
                    f"sharded pool is not implemented: the conv tail "
                    f"is stored in the pool's dtype (f32 or bf16), "
                    f"unscaled, on one device")
            rows = int(max_slots) + 1
            self.ssm = jnp.zeros((state.n_layers, rows, *state.ssm),
                                 jnp.float32)
            self.conv = jnp.zeros((state.n_layers, rows, *state.conv),
                                  self.policy.store_dtype)
            self._state_bytes_per_slot = int(
                (self.ssm.nbytes + self.conv.nbytes) // rows)
        # the sliding layers' rings (module docstring); None = every
        # layer pages all of a sequence
        self.window = window
        self.wk = self.wv = None
        self.window_owner: List[Optional[int]] = []
        if window is not None:
            if (self.policy.scaled or sharding is not None
                    or state is not None or latent is not None
                    or prefix_cache
                    or not jnp.issubdtype(self.policy.store_dtype,
                                          jnp.floating)
                    or jnp.dtype(self.policy.store_dtype).itemsize < 2):
                raise NotImplementedError(
                    f"a window store beside a {self.policy.name!r}, "
                    f"sharded, latent or recurrent pool, or under the "
                    f"prefix cache, is not implemented: the rings are "
                    f"stored in the pool's dtype (f32 or bf16), "
                    f"unscaled, on one device, and hold nothing a "
                    f"second sequence could share")
            if window.ring < window.window or int(max_slots) < 1:
                raise ValueError(
                    f"need ring >= window and max_slots >= 1; got "
                    f"{window}, max_slots={max_slots}")
            rows = (int(max_slots) + 1) * window.ring
            self.wk = jnp.zeros((window.n_layers, rows, shape[2]),
                                self.policy.store_dtype)
            self.wv = jnp.zeros_like(self.wk)
            self.window_owner = [None] * int(max_slots)
        # LIFO free list: reuse recently-freed blocks first (warm pages).
        # The membership set keeps release's double-free check O(1)
        # instead of an O(free-list) scan per block.
        self._free: List[int] = list(range(num_blocks - 1, NULL_BLOCK, -1))
        self._free_set: Set[int] = set(self._free)
        self._ref: List[int] = [0] * num_blocks
        # prefix index: token-prefix bytes -> block id (and its inverse)
        self._index: Dict[bytes, int] = {}
        self._block_key: Dict[int, bytes] = {}
        self._block_fill: Dict[int, int] = {}     # published slots used
        # refcount-zero published blocks, retained for reuse until the
        # free list runs dry; evicted least-recently-touched first
        self._cached_free: Set[int] = set()
        self._lru: Dict[int, int] = {}
        self._touch_counter = 0
        # lazy-deletion eviction heap over (touch stamp, block): every
        # touch pushes, eviction pops until an entry matches the
        # block's CURRENT stamp — O(log touches) per eviction instead
        # of min() over the whole retention set, which matters once
        # eviction means a device->host demotion copy
        self._lru_heap: List[Tuple[int, int]] = []
        # host-RAM second tier (serve/kv_tier.py): eviction demotes
        # published blocks here instead of destroying them. Meaningful
        # only under the prefix cache — there is nothing to spill when
        # nothing is retained.
        self.host_tier = host_tier if self.prefix_cache else None
        # eviction counter (hit accounting lives in ServeMetrics,
        # which sees per-admission cached-token counts)
        self.cache_evictions = 0
        # blocks acquired for a SPECULATIVE tail (serve/spec.py):
        # referenced like any private block, but their slots hold
        # unverified draft KV until the engine commits or rolls back —
        # the prefix index must never see them (publish() refuses)
        self._tentative: Set[int] = set()

    # ---- accounting -------------------------------------------------
    @property
    def bytes_per_block(self) -> int:
        """Device bytes one block costs under this pool's layout
        policy (k + v slot data across layers + the per-block scale
        rows when scaled). Policy-aware: int8 blocks cost ~1/4 of f32
        ones, so the same pool bytes hold ~4x the blocks — THE
        capacity-is-concurrency equation (tests/test_kv_quant.py
        solves it for equal bytes)."""
        both = self.policy.bytes_per_block(
            n_layers=self.n_layers, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, block_size=self.block_size)
        # a latent pool has the one row kind (unscaled: exactly half)
        return both // 2 if self.latent is not None else both

    @property
    def pool_bytes(self) -> int:
        """Total device bytes of the pool's KV storage (+ scales)."""
        return self.num_blocks * self.bytes_per_block

    @property
    def bytes_per_token(self) -> float:
        """Device bytes one resident token position costs."""
        return self.bytes_per_block / self.block_size

    @property
    def state_bytes_per_slot(self) -> int:
        """Device bytes of ONE slot's recurrent state over all the
        recurrent layers, whatever the sequence's length (0 for a
        KV-only family). ``bytes_per_token`` counts the layers that
        hold KV only."""
        return self._state_bytes_per_slot

    @property
    def window_bytes_per_slot(self) -> int:
        """Device bytes of ONE slot's rings over all the sliding-window
        layers, whatever the sequence's length (0 without a window
        store). ``bytes_per_token`` counts the global layers only."""
        if self.window is None:
            return 0
        w = self.window
        return int(2 * w.n_layers * w.ring * self.n_kv_heads
                   * self.head_dim * jnp.dtype(self.wk.dtype).itemsize)

    @property
    def window_slots_free(self) -> int:
        """Rings no sequence owns."""
        return sum(1 for o in self.window_owner if o is None)

    def window_acquire(self, slot: int, owner: int) -> None:
        """Slot ``slot``'s rings now belong to sequence ``owner`` (a
        request id). Nothing is cleared: a sequence that starts at
        position 0 masks every row it has not written."""
        if self.window_owner[slot] is not None:
            raise ValueError(
                f"ring {slot} is owned by {self.window_owner[slot]}")
        self.window_owner[slot] = int(owner)

    def window_release(self, slot: int) -> None:
        if self.window is not None:
            self.window_owner[slot] = None

    @property
    def usable_blocks(self) -> int:
        """Blocks available to requests (null block excluded)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        """Truly free blocks (not referenced, not cached)."""
        return len(self._free)

    @property
    def num_cached(self) -> int:
        """Refcount-zero blocks retained by the prefix index —
        reusable as cache hits, evictable on demand."""
        return len(self._cached_free)

    @property
    def num_available(self) -> int:
        """Blocks an acquire can produce: free + evictable cached."""
        return len(self._free) + len(self._cached_free)

    @property
    def num_used(self) -> int:
        """Blocks referenced by at least one live block table."""
        return self.usable_blocks - self.num_free - self.num_cached

    @property
    def utilization(self) -> float:
        return self.num_used / max(self.usable_blocks, 1)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` token slots."""
        return -(-n_tokens // self.block_size)

    def can_acquire(self, n: int) -> bool:
        return n <= self.num_available

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def is_cached(self, block: int) -> bool:
        """Is the block referenced by the prefix index (published)?"""
        return block in self._block_key

    # ---- acquire / release ------------------------------------------
    def _touch(self, b: int) -> None:
        self._touch_counter += 1
        self._lru[b] = self._touch_counter
        heapq.heappush(self._lru_heap, (self._touch_counter, b))
        if len(self._lru_heap) > 8 * self.num_blocks + 64:
            # lazy-deletion debt outgrew the pool: rebuild from the
            # live stamps (at most one entry per touched block)
            self._lru_heap = [(s, blk) for blk, s in self._lru.items()]
            heapq.heapify(self._lru_heap)

    def _evict_lru(self) -> int:
        """Drop the least-recently-touched refcount-zero cached block
        from the index and hand it back as a plain free block —
        demoting its slot data to the host tier first when one is
        attached, so the chain survives as a host-hit instead of
        costing a future re-prefill. Only unreferenced blocks are
        candidates, so an evicted block is — by construction —
        unreachable from every live block table. Heap entries whose
        stamp is no longer the block's current one are stale (the
        block was re-touched, re-referenced, or already evicted) and
        are discarded on pop."""
        while self._lru_heap:
            stamp, b = heapq.heappop(self._lru_heap)
            if b in self._cached_free and self._lru.get(b) == stamp:
                break
        else:
            # unreachable while the heap invariant holds (every cached
            # block's latest touch is in the heap); kept as a guard so
            # a bookkeeping bug degrades to the old O(n) scan instead
            # of corrupting the allocator
            b = min(self._cached_free, key=self._lru.__getitem__)
        if self.host_tier is not None:
            self._demote(b)
        self._cached_free.remove(b)
        self._unpublish(b)
        self.cache_evictions += 1
        return b

    def _demote(self, b: int) -> bool:
        """Copy published block ``b`` to the host tier before eviction
        destroys it: one export-format record — the full block's slot
        data exactly as stored (``store_dtype``) plus its scale rows
        when the policy is scaled — keyed by the block's prefix-index
        key, so the host tier walks the same key ladder the device
        index does. A device->host copy on the ALLOCATION path only:
        the engine's step phasing keeps it off every decode dispatch."""
        key = self._block_key.get(b)
        fill = self._block_fill.get(b, 0)
        if key is None or fill <= 0:
            return False
        bs = self.block_size
        rec = {"fill": int(fill), **self._record(
            *self.read_slots(np.arange(b * bs, (b + 1) * bs)))}
        if self.policy.scaled:
            rec["k_scale"] = np.asarray(self.k_scale[:, b])
            rec["v_scale"] = np.asarray(self.v_scale[:, b])
        return self.host_tier.put(key, rec)

    def _unpublish(self, b: int) -> None:
        key = self._block_key.pop(b, None)
        if key is not None and self._index.get(key) == b:
            del self._index[key]
        self._block_fill.pop(b, None)
        self._lru.pop(b, None)

    def acquire(self, n: int) -> Optional[List[int]]:
        """Take ``n`` private blocks (refcount 1 each): pop the LIFO
        free list first, then evict LRU cached blocks. Returns None if
        even eviction cannot cover ``n`` (caller decides whether to
        wait or preempt — the pool never partially allocates)."""
        if n > self.num_available:
            return None
        taken: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
                self._free_set.remove(b)
            else:
                b = self._evict_lru()
            self._ref[b] = 1
            taken.append(b)
        return taken

    def acquire_cached(self, blocks: Sequence[int]) -> None:
        """Pin cached/shared blocks for one more holder (a cache hit:
        the admitting request's table will reference them, or a
        transient COW-source pin for the duration of one prefill).
        Refcount-zero blocks leave the evictable retention set."""
        for b in blocks:
            if self._ref[b] == 0:
                if b not in self._cached_free:
                    raise ValueError(
                        f"block {b} is neither referenced nor cached — "
                        f"cannot acquire it as a prefix hit")
                self._cached_free.remove(b)
            self._ref[b] += 1
            self._touch(b)

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per listed block. A block reaching
        refcount zero returns to the free list — unless it is published
        in the prefix index, in which case it is RETAINED (evictable,
        LRU) for future prefix hits. O(1) per block."""
        need: Dict[int, int] = {}
        for b in blocks:
            if not (NULL_BLOCK < b < self.num_blocks):
                raise ValueError(f"releasing invalid block id {b}")
            need[b] = need.get(b, 0) + 1
            if b in self._free_set or need[b] > self._ref[b]:
                raise ValueError(f"double free of block {b}")
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                if b in self._block_key:
                    self._cached_free.add(b)
                else:
                    self._free.append(b)
                    self._free_set.add(b)

    # ---- tentative (speculative-tail) blocks -------------------------
    def is_tentative(self, block: int) -> bool:
        return block in self._tentative

    @property
    def num_tentative(self) -> int:
        return len(self._tentative)

    def tentative_acquire(self, n: int) -> Optional[List[int]]:
        """Take ``n`` private blocks for a SPECULATIVE tail: drafted
        slots will be written into them before verification resolves.
        Same allocator as :meth:`acquire` (free list first, then LRU
        eviction, never partial), but the blocks are marked tentative
        until :meth:`commit_tentative` or :meth:`rollback_tentative` —
        the engine resolves every tentative block within the step that
        acquired it, so publish/index state never observes one."""
        got = self.acquire(n)
        if got is not None:
            self._tentative.update(got)
        return got

    def commit_tentative(self, blocks: Sequence[int]) -> None:
        """Verification accepted drafts reaching into ``blocks``: they
        become ordinary private blocks of the owning request (the
        refcount they already hold is the request's table reference)."""
        for b in blocks:
            if b not in self._tentative:
                raise ValueError(f"block {b} is not tentative")
            self._tentative.remove(b)

    def rollback_tentative(self, blocks: Sequence[int]) -> None:
        """Verification rejected the drafts in ``blocks``: drop the
        speculative reference and return them to the allocator. The
        draft KV they hold is garbage nobody can reach — the blocks
        were never published and leave every live table now."""
        for b in blocks:
            if b not in self._tentative:
                raise ValueError(f"block {b} is not tentative")
            self._tentative.remove(b)
        self.release(blocks)

    # legacy names (PR 1 surface): plain allocation without sharing
    def alloc(self, n: int) -> Optional[List[int]]:
        return self.acquire(n)

    def free(self, blocks: Sequence[int]) -> None:
        self.release(blocks)

    def can_alloc(self, n: int) -> bool:
        return self.can_acquire(n)

    # ---- prefix index -----------------------------------------------
    @staticmethod
    def _key(tokens: np.ndarray, n: int,
             namespace: Optional[str] = None) -> bytes:
        """Index key for ``tokens[:n]``. ``namespace`` partitions the
        index (multi-tenant LoRA serving, serve/adapters.py): identical
        token prefixes hold DIFFERENT KV under different adapters, so a
        chain cached under one adapter must never hit for another (or
        for the base model). EVERY key is a NUL-terminated namespace
        prefix (empty for the base model) + the literal token bytes —
        adapter ids cannot contain NUL, so the first NUL always delimits
        the namespace and two keys are equal only when both namespace
        and token prefix are (a bare token-bytes base key could collide
        with an id whose bytes happen to open another key's body)."""
        body = np.ascontiguousarray(tokens[:n], dtype=np.int32).tobytes()
        if namespace is None:
            return b"\x00" + body
        return namespace.encode("utf-8") + b"\x00" + body

    def lookup(self, tokens, max_tokens: Optional[int] = None, *,
               namespace: Optional[str] = None) -> AdmitPlan:
        """Longest cached block-chain for ``tokens``: full blocks are
        matched at block boundaries, then the longest published partial
        leaf extending the chain. The match is capped at
        ``max_tokens`` (callers pass ``len(tokens) - 1`` so at least
        one token is always prefilled — prefill must produce the
        next-token logits). ``namespace``: the requesting adapter id
        (chains are shared per adapter — see :meth:`_key`). Read-only;
        returns a plan with ``n_new_blocks`` unset."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        limit = len(tokens) if max_tokens is None else min(
            int(max_tokens), len(tokens))
        if not self.prefix_cache or limit <= 0:
            return AdmitPlan(cached_tokens=0)
        bs = self.block_size
        full: List[int] = []
        while (len(full) + 1) * bs <= limit:
            b = self._index.get(self._key(tokens, (len(full) + 1) * bs,
                                          namespace))
            if b is None:
                break
            full.append(b)
        m = len(full) * bs
        cow_src, cow_len = None, 0
        for f in range(min(bs - 1, limit - m), 0, -1):
            b = self._index.get(self._key(tokens, m + f, namespace))
            if b is not None:
                cow_src, cow_len = b, f
                break
        return AdmitPlan(cached_tokens=m + cow_len, shared_blocks=full,
                         cow_src=cow_src, cow_len=cow_len)

    def plan_admission(self, tokens, total_tokens: int, *,
                       namespace: Optional[str] = None) -> AdmitPlan:
        """Best ADMISSIBLE plan for a request whose table must cover
        ``total_tokens`` slots (prefill length + the first decode
        write): the longest cached chain plus the private blocks that
        complete the table. Only ``n_new_blocks`` must come from the
        allocator — the admission budget counts uncached blocks only.

        A maximal chain is not always admissible: pinning it removes
        its blocks from the evictable set, and the transient COW pin
        occupies one more block than the table itself, so near the
        capacity edge the longest-hit plan can need more simultaneous
        blocks than the pool holds — FOREVER, since nothing else would
        ever evict the pinned chain. Rather than wedge the queue head
        (and everything behind it), degrade: drop the COW hit first,
        then fall back to a cache-cold plan, which is admissible
        whenever the request can run at all (submit-time fail-fast
        checked ``blocks_for(total) <= usable_blocks``)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n_total = self.blocks_for(int(total_tokens))
        plan = self.lookup(tokens, max_tokens=len(tokens) - 1,
                           namespace=namespace)
        plan.n_new_blocks = n_total - len(plan.shared_blocks)
        plan.window_bytes = self.window_bytes_per_slot
        if self.can_admit(plan) or not plan.pinned_blocks:
            return plan
        if plan.cow_src is not None:
            plan = AdmitPlan(
                cached_tokens=len(plan.shared_blocks) * self.block_size,
                shared_blocks=plan.shared_blocks,
                n_new_blocks=plan.n_new_blocks)
            if self.can_admit(plan):
                return plan
        return AdmitPlan(cached_tokens=0, n_new_blocks=n_total)

    def can_admit(self, plan: AdmitPlan) -> bool:
        """Can ``plan.n_new_blocks`` be acquired once the plan's own
        chain is pinned? Pinned blocks stop being eviction candidates,
        so they must not be counted as available."""
        pinned_evictable = sum(1 for b in plan.pinned_blocks
                               if b in self._cached_free)
        if plan.window_bytes and not self.window_slots_free:
            return False        # both kinds, or not at all
        return plan.n_new_blocks <= self.num_available - pinned_evictable

    def publish(self, tokens, blocks: Sequence[int], n_tokens: int, *,
                namespace: Optional[str] = None) -> None:
        """Index ``blocks`` as the cached chain for
        ``tokens[:n_tokens]`` (the retire/preempt path — instead of
        freeing, make the request's KV findable). Full blocks are keyed
        at block boundaries; a trailing partial block at its exact
        count. ``namespace``: the adapter id whose programs WROTE this
        KV — the chain is findable only by requests bound to the same
        adapter (see :meth:`_key`). A key already mapping to a
        DIFFERENT block (an identical request published first) keeps
        the incumbent — the duplicate stays unpublished and will return
        to the free list on release. Publish BEFORE release: release
        retains published blocks."""
        if not self.prefix_cache or n_tokens <= 0:
            return
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n_tokens = min(int(n_tokens), len(tokens))
        q, f = divmod(n_tokens, self.block_size)
        used = q + (1 if f else 0)
        bad = [b for b in blocks[:used] if b in self._tentative]
        if bad:
            # the invariant speculative decoding must never break:
            # cached/published chains hold COMMITTED positions only —
            # a tentative block here means the engine tried to publish
            # an unresolved speculative tail
            raise ValueError(
                f"publish would index tentative block(s) {bad}: "
                f"speculative drafts must be committed or rolled back "
                f"before a request's blocks are published")
        for j in range(q):
            self._publish_one(blocks[j], self._key(tokens, (j + 1)
                                                   * self.block_size,
                                                   namespace),
                              self.block_size)
        if f and q < len(blocks):
            self._publish_one(blocks[q],
                              self._key(tokens, n_tokens, namespace), f)

    def _publish_one(self, b: int, key: bytes, fill: int) -> None:
        cur = self._index.get(key)
        if cur == b:
            self._touch(b)
            return
        if cur is not None:
            return  # identical content already cached under this key
        if b in self._block_key:
            # already indexed under another key (cannot happen through
            # the engine: a block holds exactly one chain position) —
            # keep the existing mapping rather than corrupt the index
            return
        self._index[key] = b
        self._block_key[b] = key
        self._block_fill[b] = fill
        self._touch(b)

    # ---- host tier: combined walk, peek, promotion -------------------
    def _walk_chain(self, tokens: np.ndarray, limit: int,
                    namespace: Optional[str]) -> Tuple[int, List[Tuple]]:
        """The longest chain covering ``tokens[:limit]`` from EITHER
        tier: full blocks at block boundaries, then the longest partial
        leaf, exactly the :meth:`lookup` walk — but a boundary missing
        from the device index may be satisfied by a host-tier record.
        Returns ``(covered_tokens, entries)`` with entries in chain
        order: ``("dev", block, fill)`` for device-resident blocks,
        ``("host", key, fill)`` for host-resident ones. Read-only (host
        probes use :meth:`HostTier.contains`, which does not touch the
        tier's LRU)."""
        entries: List[Tuple] = []
        if not self.prefix_cache or limit <= 0:
            return 0, entries
        tier = self.host_tier
        bs = self.block_size
        n = 0
        while (n + 1) * bs <= limit:
            key = self._key(tokens, (n + 1) * bs, namespace)
            b = self._index.get(key)
            if b is not None:
                entries.append(("dev", b, bs))
            elif tier is not None and tier.contains(key):
                entries.append(("host", key, bs))
            else:
                break
            n += 1
        m = n * bs
        for f in range(min(bs - 1, limit - m), 0, -1):
            key = self._key(tokens, m + f, namespace)
            b = self._index.get(key)
            if b is not None:
                entries.append(("dev", b, f))
                m += f
                break
            if tier is not None and tier.contains(key):
                entries.append(("host", key, f))
                m += f
                break
        return m, entries

    def peek_chain_tokens(self, tokens, *,
                          namespace: Optional[str] = None) -> int:
        """Token positions this pool could serve warm for ``tokens`` —
        the device chain PLUS its host-tier extension. No data moves
        and nothing is pinned or touched: this is the cheap probe the
        fleet's tier peer lookup sends every replica (``kv_peek``)
        before deciding whom to pull a chain from."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        covered, _entries = self._walk_chain(tokens, len(tokens),
                                             namespace)
        return covered

    def plan_promotion(self, tokens, max_tokens: Optional[int] = None,
                       *, namespace: Optional[str] = None,
                       ) -> Tuple[int, List[bytes]]:
        """The host-resident boundaries a promotion must import so the
        DEVICE chain covers everything the combined walk can. Returns
        ``(covered_tokens, host_keys)`` — empty ``host_keys`` means
        there is nothing to promote (pure device hit, or a miss in
        both tiers). The third admission outcome in one probe:
        device-hit (covered > 0, no keys), host-hit (keys to promote),
        miss (covered == 0)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        limit = len(tokens) if max_tokens is None else min(
            int(max_tokens), len(tokens))
        if self.host_tier is None:
            return 0, []
        covered, entries = self._walk_chain(tokens, limit, namespace)
        keys = [e[1] for e in entries if e[0] == "host"]
        return covered, keys

    def promote_chain(self, keys: Sequence[bytes], *,
                      max_blocks: Optional[int] = None,
                      ) -> Tuple[int, int]:
        """Re-promote up to ``max_blocks`` host-tier records into
        freshly acquired device blocks — ONE fused scatter per pool
        array, the same device-write shape as :meth:`import_chain`, so
        promotion compiles nothing new — publishing each under its own
        boundary key and releasing (the chain lands refcount-zero in
        the retention set, an ordinary device prefix hit for the next
        admission).

        Returns ``(keys_consumed, blocks_promoted)``: the caller (the
        engine's per-step promotion feed) advances its cursor by the
        first and charges the second against its budget. Keys already
        device-resident are consumed for free. A key missing from the
        host tier (its record was budget-evicted while the promotion
        was in flight) TRUNCATES the chain: later records could never
        be reached past the gap by a device walk, so the remainder is
        consumed unpromoted and admission re-prefills from the gap —
        degraded, never wrong."""
        keys = list(keys)
        if self.host_tier is None or not keys:
            return len(keys), 0
        budget = len(keys) if max_blocks is None else max(0,
                                                          int(max_blocks))
        avail = self.num_available
        taken = 0
        todo: List[Tuple[bytes, Dict]] = []
        terminal = False
        for key in keys:
            if key in self._index:
                taken += 1
                continue
            if len(todo) >= budget or len(todo) >= avail:
                break       # out of budget/capacity — retry next step
            rec = self.host_tier.get(key)
            if rec is None:
                terminal = True
                break
            todo.append((key, rec))
            taken += 1
        if todo:
            blocks = self.acquire(len(todo))
            assert blocks is not None  # len(todo) <= num_available
            bs = self.block_size
            idx = np.concatenate([np.arange(b * bs, (b + 1) * bs)
                                  for b in blocks])
            k, v = self.write_slots(
                idx, *self._joined([r for _, r in todo]))
            if self.policy.scaled:
                barr = np.asarray(blocks, np.int32)
                ks = np.stack([np.asarray(r["k_scale"])
                               for _, r in todo], axis=1)
                vs = np.stack([np.asarray(r["v_scale"])
                               for _, r in todo], axis=1)
                self.update(k, v,
                            self.k_scale.at[:, barr].set(
                                jnp.asarray(ks, jnp.float32)),
                            self.v_scale.at[:, barr].set(
                                jnp.asarray(vs, jnp.float32)))
            else:
                self._adopt(k, v)
            for b, (key, rec) in zip(blocks, todo):
                self._publish_one(b, key, int(rec["fill"]))
            self.release(blocks)
            self.host_tier.promotions += len(todo)
            self.host_tier.promoted_tokens += sum(
                int(r["fill"]) for _, r in todo)
        if terminal:
            taken = len(keys)
        return taken, len(todo)

    # ---- chain export / import (disaggregated KV handoff) -----------
    def export_chain(self, tokens, *,
                     namespace: Optional[str] = None) -> Optional[Dict]:
        """Snapshot the longest PUBLISHED chain for ``tokens`` as host
        data — the prefill→decode handoff payload of the disaggregated
        fleet (fleet/wire.py frames it, fleet/proc.py ships it). Each
        record carries one block's slot data exactly as stored (the
        policy's ``store_dtype`` — int8 blocks export as int8, ~4x
        smaller than f32) plus its per-block-per-head scale rows when
        the policy is scaled, so an import is a byte-exact replica of
        the source blocks. When a host tier is attached the chain is
        assembled ACROSS tiers: device-resident boundaries come from
        the fused pool gather, host-resident ones from their demoted
        records (already host bytes, zero device traffic) — so a
        replica can serve its whole retained working set to a peer,
        not just the slice that happens to sit in HBM. Returns ``None``
        when nothing is cached for the prefix (evicted from both
        tiers, or never published). Read-only: refcounts, the index
        and the LRUs are untouched beyond a touch."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        covered, entries = self._walk_chain(tokens, len(tokens),
                                            namespace)
        if not entries:
            return None
        bs = self.block_size
        # ONE gather per pool array for the device-resident blocks
        # (then split host-side), not one device op per block: a chain
        # transfer must cost O(chain bytes), never O(blocks * pool
        # bytes)
        dev = [(j, e[1]) for j, e in enumerate(entries)
               if e[0] == "dev"]
        if dev:
            idx = np.concatenate([np.arange(b * bs, (b + 1) * bs)
                                  for _, b in dev])
            k_all, v_all = self.read_slots(idx)
            if self.policy.scaled:
                barr = np.asarray([b for _, b in dev], np.int32)
                ks_all = np.asarray(self.k_scale[:, barr])
                vs_all = np.asarray(self.v_scale[:, barr])
        dev_slot = {j: s for s, (j, _b) in enumerate(dev)}
        records: List[Dict] = []
        n_out = 0
        for j, (kind, ref, fill) in enumerate(entries):
            if kind == "dev":
                s = dev_slot[j]
                rec = {"fill": int(fill), **self._record(
                    k_all[:, s * bs:(s + 1) * bs],
                    None if v_all is None
                    else v_all[:, s * bs:(s + 1) * bs])}
                if self.policy.scaled:
                    rec["k_scale"] = ks_all[:, s]
                    rec["v_scale"] = vs_all[:, s]
            else:
                rec = self.host_tier.get(ref)
                if rec is None:
                    # cannot happen single-threaded (the walk just saw
                    # it); truncate at the gap rather than ship a
                    # chain with a hole
                    break
            records.append(rec)
            n_out += int(fill)
        if not records:
            return None
        return {"tokens": tokens[:n_out].copy(),
                "n_tokens": int(n_out),
                "policy": self.policy.name,
                "block_size": bs,
                "n_layers": self.n_layers,
                "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim,
                "blocks": records}

    def _check_chain_geometry(self, chain: Dict) -> None:
        mine = {"policy": self.policy.name,
                "block_size": self.block_size,
                "n_layers": self.n_layers,
                "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim}
        theirs = {k: chain[k] for k in mine}
        if theirs != mine:
            diffs = {k: (theirs[k], mine[k]) for k in mine
                     if theirs[k] != mine[k]}
            raise ValueError(
                f"KV chain layout does not match this pool "
                f"({{field: (chain, pool)}} = {diffs}) — the exporting "
                f"and importing engines must be built from the same "
                f"spec (same KV layout policy and pool geometry)")

    def import_chain(self, chain: Dict, *,
                     namespace: Optional[str] = None) -> int:
        """Admit an exported chain as a warm prefix hit: allocate
        private blocks, write the transferred slot data (and scales)
        into them byte-exactly, PUBLISH them under the chain's token
        prefix, and release — published refcount-zero blocks are
        retained in the LRU exactly like a retired request's, so the
        next admission for this prefix hits instead of re-prefilling.
        Returns the number of token positions now served from cache
        (0 when the pool cannot hold any of the chain or the prefix
        cache is off — the caller's fallback is local re-prefill,
        which is always correct). A chain LARGER than the pool can
        hold is not discarded: the longest block-aligned prefix that
        fits is imported instead — the chain is cache, so a partial
        import is always correct and still saves that many prefill
        tokens (the dropped tail includes any partially-filled leaf).
        Keys already published keep their incumbent block (the
        duplicate import frees on release), so a racing local prefill
        can never be corrupted by a late handoff."""
        self._check_chain_geometry(chain)
        records = chain["blocks"]
        n_tokens = int(chain["n_tokens"])
        if not self.prefix_cache or n_tokens <= 0 or not records:
            return 0
        q, f = divmod(n_tokens, self.block_size)
        if len(records) != q + (1 if f else 0):
            raise ValueError(
                f"KV chain block count {len(records)} does not cover "
                f"n_tokens={n_tokens} at block_size={self.block_size}")
        n_fit = min(len(records), self.num_available)
        if n_fit <= 0:
            return 0
        if n_fit < len(records):
            records = records[:n_fit]
            n_tokens = n_fit * self.block_size
        blocks = self.acquire(len(records))
        if blocks is None:  # unreachable: capacity checked above
            return 0
        bs = self.block_size
        # ONE fused scatter per pool array — a per-block .at[].set
        # would copy the whole pool once per block (O(blocks * pool
        # bytes)); this is the decode-replica hot path during a
        # handoff and must not stall decode steps behind pool-sized
        # memcpys
        idx = np.concatenate([np.arange(b * bs, (b + 1) * bs)
                              for b in blocks])
        k, v = self.write_slots(idx, *self._joined(records))
        if self.policy.scaled:
            barr = np.asarray(blocks, np.int32)
            ks = np.stack([np.asarray(r["k_scale"]) for r in records],
                          axis=1)
            vs = np.stack([np.asarray(r["v_scale"]) for r in records],
                          axis=1)
            k_scale = self.k_scale.at[:, barr].set(
                jnp.asarray(ks, jnp.float32))
            v_scale = self.v_scale.at[:, barr].set(
                jnp.asarray(vs, jnp.float32))
            self.update(k, v, k_scale, v_scale)
        else:
            self._adopt(k, v)
        tokens = np.asarray(chain["tokens"], np.int32).reshape(-1)
        self.publish(tokens, blocks, n_tokens, namespace=namespace)
        self.release(blocks)
        return n_tokens

    # ---- the pool's edge: host records <-> device rows ---------------
    @staticmethod
    def _record(k, v) -> Dict:
        """A host record's data: ``k`` and, where the pool has one, ``v``
        (a latent pool's records carry no ``v``)."""
        return {"k": k} if v is None else {"k": k, "v": v}

    def _joined(self, records):
        """(k, v) of ``records`` side by side along the slot dim, ``v``
        None for a latent pool: what :meth:`write_slots` takes."""
        return tuple(
            np.concatenate([np.asarray(r[n]) for r in records], axis=1)
            if records[0].get(n) is not None else None
            for n in ("k", "v"))

    def _adopt(self, k, v) -> None:
        """:meth:`update` with what :meth:`write_slots` returned."""
        self.update(*((k,) if v is None else (k, v)))

    def read_slots(self, idx) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Flat slots ``idx`` [n] of every layer as HOST arrays in the
        record shape ``[L, n, H_kv, Dh]`` (k, v), exactly as stored
        (``store_dtype``), the pad lanes dropped; ``v`` is None for a
        latent pool. One gather a pool array: a chain read costs
        O(chain bytes), never O(pool)."""
        every = np.arange(self.n_layers)[:, None]

        def heads(pool):
            # (layer, slot) pairs, as the programs address the pool: an
            # index over all layers at once re-lays the whole pool on
            # the chip (PERF.md, PR 28)
            rows = np.asarray(pool[every, np.asarray(idx)[None, :]])
            L, n = rows.shape[:2]
            local = self.n_kv_heads // self.head_shards * self.head_dim
            return rows.reshape(L, n, self.head_shards, self._local_width)[
                ..., :local].reshape(L, n, self.n_kv_heads, self.head_dim)

        return heads(self.k), (None if self.v is None else heads(self.v))

    def write_slots(self, idx, k_new, v_new=None):
        """The pool arrays with records ``k_new``/``v_new``
        ``[L, n, H_kv, Dh]`` written at flat slots ``idx`` [n] — ONE
        fused scatter a pool array (a per-block ``.at[].set`` would copy
        the whole pool once per block). Returns ``(k, v)`` for
        :meth:`update`; the pad lanes are written as zeros."""
        every = np.arange(self.n_layers)[:, None]

        def put(pool, new):
            new = np.asarray(new)
            L, n = new.shape[:2]
            rows = new.reshape(L, n, self.head_shards, -1)
            pad = self._local_width - rows.shape[-1]
            if pad:
                rows = np.pad(rows, ((0, 0),) * 3 + ((0, pad),))
            return pool.at[every, np.asarray(idx)[None, :]].set(
                jnp.asarray(rows.reshape(L, n, -1),
                            self.policy.store_dtype))

        return put(self.k, k_new), (None if self.v is None
                                    else put(self.v, v_new))

    # ---- device views ----------------------------------------------
    def caches(self):
        """The pool's device arrays, as carried through the jitted step
        functions (the engine writes the returned/donated results back
        via :meth:`update`): ``(k, v)`` for passthrough policies,
        ``(k, v, k_scale, v_scale)`` for scaled ones, ``(k, v, ssm,
        conv)`` for a recurrent family, ``(k, v, wk, wv)`` for a window
        one, ``(k,)`` for a latent one and ``(k, ssm, conv)`` for one
        that is latent AND recurrent —
        call sites splat the tuple, so neither the policy nor the
        family changes their shape."""
        if self.latent is not None:
            if self.state is not None:
                return self.k, self.ssm, self.conv
            return (self.k,)
        if self.policy.scaled:
            return self.k, self.v, self.k_scale, self.v_scale
        if self.state is not None:
            return self.k, self.v, self.ssm, self.conv
        if self.window is not None:
            return self.k, self.v, self.wk, self.wv
        return self.k, self.v

    def update(self, k, *rest) -> None:
        """Adopt what a program returned for :meth:`caches`' buffers,
        in that order."""
        if 1 + len(rest) != len(self.caches()):
            carries = ("the one latent buffer and the recurrent state "
                       "buffers" if self.latent is not None
                       and self.state is not None
                       else "the one latent buffer"
                       if self.latent is not None
                       else "scale arrays" if self.policy.scaled
                       else "recurrent state buffers"
                       if self.state is not None
                       else "the window store's buffers"
                       if self.window is not None else "no other buffers")
            raise ValueError(
                f"policy {self.policy.name!r} carries {carries}; "
                f"update() needs all {len(self.caches())} pool buffers, "
                f"got {1 + len(rest)}")
        self.k = k
        if self.latent is not None:
            if self.state is not None:
                self.ssm, self.conv = rest
            return
        self.v, *rest = rest
        if self.policy.scaled:
            self.k_scale, self.v_scale = rest
        elif self.state is not None:
            self.ssm, self.conv = rest
        elif self.window is not None:
            self.wk, self.wv = rest

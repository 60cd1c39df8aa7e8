"""Block-paged KV cache: the allocator + the pure cache-update rules.

Reference parity: the block-table KV management behind
block_multihead_attention (fusion/gpu/block_multi_head_attention_kernel.cu)
— PagedAttention's (Kwon et al.) block-granular allocation, so a serving
engine's HBM footprint tracks the TOKENS ACTUALLY HELD rather than
max_len * max_batch.

Pieces:
  * `BlockAllocator` — host-side free list over a fixed block pool.
    Block 0 is the reserved TRASH block: every in-program write whose
    destination must be masked out (padded prefill positions, padded
    decode slots) is routed there instead of carrying a scatter mask —
    copy-free release is then trivial (free the ids; nothing is zeroed,
    stale contents are never attended to because the length mask bounds
    every read and appends overwrite before reads reach them).
  * `PagedKVCache` — the device arrays: `[L, num_blocks, H_kv,
    block_size, D]` per k/v (layer axis outermost, so that the step
    programs see the pool as `[L * num_blocks, ...]` for free and their
    `lax.scan` over stacked layer weights addresses layer l's blocks at
    `l * num_blocks + id`), plus per-(layer, block) f32 scales when the
    storage dtype is int8.
  * pure jnp functions used INSIDE the compiled step programs: decode
    append (scatter one token per slot through the block table) and
    prefill scatter (page-granular), each with an int8 variant that
    requantizes the touched block against its per-block scale.

Static shapes everywhere: block tables are padded [slots, pages] arrays,
the trash block absorbs masked writes, and the allocator is the only
dynamic piece — it lives on the host and never enters a trace.

Round 13 adds PREFIX CACHING on top of the same block pool (vLLM's
block-hash reuse): `PrefixCache` keys FULL blocks by a rolling content
hash over their token ids (chained, so a block's identity includes its
whole prefix) and refcounts every block a live request's table holds.
A request whose prompt shares a cached prefix points its table rows at
the cached blocks (zero prefill for those pages); `release` returns
hash-mapped blocks to an LRU of refcount-0 cached blocks instead of the
free list, and allocation under pressure evicts from that LRU — never
from a block something still references.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from ..core.lockdep import ThreadContract
from ..ops.quantized import INT4_QMAX, int4_pack, int4_unpack

#: block id 0 is never allocated — masked writes land there (see module doc)
TRASH_BLOCK = 0


class BlockAllocator:
    """Free-list allocator over `num_blocks` cache blocks (block 0
    reserved as trash). Allocation is all-or-nothing: a request either
    gets its full block budget up front (admission control) or stays
    queued — no mid-flight OOM/preemption.

    THREAD CONTRACT (D15): single-owner, lock-free by design — the
    ServingEngine shares its contract object with the pool so one owner
    thread covers the whole serving object graph
    (``FLAGS_debug_thread_checks`` asserts it)."""

    #: D15 static marker: methods the single-owner contract guards
    _thread_contract = ("alloc", "free")

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the trash block)")
        self.num_blocks = int(num_blocks)
        self.contract = ThreadContract("BlockAllocator")
        self._free = list(range(self.num_blocks - 1, 0, -1))  # pop() -> 1..

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """n block ids, or None when the pool can't cover them."""
        self.contract.check("alloc")
        if n < 0:
            raise ValueError(f"negative block count {n}")
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, ids) -> None:
        self.contract.check("free")
        for b in ids:
            b = int(b)
            if not 0 < b < self.num_blocks:
                raise ValueError(f"freeing invalid block id {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold `tokens` cache entries."""
    return -(-int(tokens) // int(block_size))


# ------------------------------------------------------- prefix caching

def hash_blocks(tokens, block_size: int, namespace: int = 0) -> list:
    """Chained content hashes for every FULL block of `tokens`: block i's
    hash covers its own token ids AND (through the chain) every token
    before it, so equal hashes mean equal whole prefixes — the property
    that makes hash->block reuse sound. `namespace` seeds the chain: KV
    content depends on the model weights / layer config / cache dtype,
    so two engines over different models must never collide (a namespace
    mismatch shows up as 0% hits on an identical-prompt stream — the D7
    cache-defeated finding). Hashes are sha256 digests, not Python
    `hash()`: a 64-bit builtin-hash collision between two different
    prefixes would silently serve one request's KV content to another
    (token ids are caller-controlled, so the weak hash is also
    adversarially reachable — the vLLM CVE-2025-25183 shape)."""
    bs = int(block_size)
    toks = np.asarray(tokens).reshape(-1).astype(np.int64)
    h = hashlib.sha256(
        b"paddle_tpu.prefix_cache:%d" % int(namespace)).digest()
    out = []
    for i in range(len(toks) // bs):
        h = hashlib.sha256(h + toks[i * bs:(i + 1) * bs].tobytes()).digest()
        out.append(h)
    return out


class PrefixCache:
    """Hash->block map + per-block refcounts + LRU over a BlockAllocator.

    Block lifecycle: `allocate` hands out private blocks at refcount 1
    (evicting refcount-0 cached blocks when the free list runs dry);
    `register` publishes a computed full block under its content hash;
    `lookup` serves a new request's shared prefix by bumping refcounts;
    `release` (the finish path) decrefs — a hash-mapped block at
    refcount 0 parks in the LRU (its KV stays warm for the next request)
    while an unmapped block goes straight back to the free list. Only
    refcount-0 blocks are ever evicted.

    THREAD CONTRACT (D15): single-owner like the engine that drives it —
    the hash map / refcounts / LRU mutate lock-free by design; the
    engine shares its ThreadContract here so one owner thread covers the
    whole serving object graph."""

    #: D15 static marker: methods the single-owner contract guards
    _thread_contract = ("allocate", "lookup", "register", "release",
                        "cancel_lookup")

    def __init__(self, allocator: BlockAllocator, max_cached_blocks: int = 0):
        self.allocator = allocator
        self.contract = ThreadContract("PrefixCache")
        #: cap on refcount-0 cached blocks (0 = bounded only by the pool)
        self.max_cached_blocks = int(max_cached_blocks)
        self._map: dict = {}          # hash -> block id (full blocks only)
        self._block_hash: dict = {}   # block id -> hash (inverse)
        self._ref: dict = {}          # block id -> refcount (live blocks)
        self._lru: OrderedDict = OrderedDict()  # refcount-0 cached blocks
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # ------------------------------------------------------------ queries
    @property
    def cached_blocks(self) -> int:
        """Blocks currently addressable by content hash."""
        return len(self._map)

    @property
    def referenced_blocks(self) -> int:
        """Hash-mapped blocks some live request still references. Mapped
        refcount-0 blocks are exactly the LRU members (release parks
        them there, ref() removes them, eviction drops both sides), so
        this is O(1) — it runs in the pool gauges on every admission and
        finish."""
        return len(self._map) - len(self._lru)

    @property
    def evictable(self) -> int:
        return len(self._lru)

    @property
    def available(self) -> int:
        """Blocks an admission could obtain: free list + evictable LRU."""
        return self.allocator.available + len(self._lru)

    def refcount(self, block_id: int) -> int:
        return self._ref.get(int(block_id), 0)

    # ------------------------------------------------------------- alloc
    def allocate(self, n: int):
        """All-or-nothing like BlockAllocator.alloc, but refcount-0 cached
        blocks count as capacity: when the free list can't cover, LRU
        blocks are evicted (hash entries dropped) to make room. Returns
        private block ids at refcount 1, or None."""
        self.contract.check("allocate")
        n = int(n)
        if n < 0:
            raise ValueError(f"negative block count {n}")
        if n > self.available:
            return None
        while self.allocator.available < n:
            self._evict_one()
        ids = self.allocator.alloc(n)
        for b in ids:
            self._ref[b] = 1
        return ids

    def _evict_one(self):
        blk, _ = self._lru.popitem(last=False)      # least recently used
        h = self._block_hash.pop(blk)
        del self._map[h]
        self._ref.pop(blk, None)
        self.allocator.free([blk])
        self.evictions += 1

    # ------------------------------------------------------------ lookup
    def lookup(self, hashes) -> list:
        """Longest cached prefix of `hashes`: consecutive from block 0.
        Found blocks get a refcount bump (and leave the LRU — a
        referenced block is never eviction-eligible). Counts hits for the
        found run and misses for the remainder."""
        self.contract.check("lookup")
        found = []
        for h in hashes:
            blk = self._map.get(h)
            if blk is None:
                break
            self.ref(blk)
            found.append(blk)
        self.hits += len(found)
        self.misses += len(hashes) - len(found)
        return found

    def ref(self, block_id: int) -> None:
        blk = int(block_id)
        self._ref[blk] = self._ref.get(blk, 0) + 1
        self._lru.pop(blk, None)

    def cancel_lookup(self, found, n_hashes: int) -> None:
        """Undo a lookup whose admission could not proceed (pool full):
        releases the refs it took and rolls the hit/miss counters back so
        blocked retries don't inflate the hit rate."""
        self.hits -= len(found)
        self.misses -= int(n_hashes) - len(found)
        self.release(found)

    # ---------------------------------------------------------- register
    def register(self, hashes, block_ids) -> None:
        """Publish computed full blocks under their content hashes (zip of
        parallel lists). A hash already mapped to a DIFFERENT block keeps
        the existing mapping (two concurrent misses computed the same
        content; the newer copy stays private and free-lists on release).
        Idempotent for already-registered pairs."""
        self.contract.check("register")
        for h, blk in zip(hashes, block_ids):
            blk = int(blk)
            if h in self._map:
                continue
            old_h = self._block_hash.get(blk)
            if old_h is not None and old_h != h:
                # the block's content moved on (it was extended past the
                # originally registered run) — rekey it
                del self._map[old_h]
            self._map[h] = blk
            self._block_hash[blk] = h

    # ------------------------------------------------------------ release
    def release(self, block_ids) -> None:
        """Decref each block; at refcount 0 a hash-mapped block parks in
        the LRU (release-to-cache) and an unmapped block free-lists. THE
        round-13 sharing contract: finish/timeout paths must come through
        here — an unconditional allocator.free() on a shared block would
        corrupt every other request pointing at it."""
        self.contract.check("release")
        for blk in block_ids:
            blk = int(blk)
            refs = self._ref.get(blk, 0)
            if refs <= 0:
                raise ValueError(f"release of unreferenced block {blk}")
            if refs > 1:
                self._ref[blk] = refs - 1
                continue
            del self._ref[blk]
            if blk in self._block_hash:
                self._lru[blk] = None
                self._lru.move_to_end(blk)
                self._trim()
            else:
                self.allocator.free([blk])

    def _trim(self):
        if self.max_cached_blocks <= 0:
            return
        while len(self._lru) > self.max_cached_blocks:
            self._evict_one()


class PagedKVCache:
    """The pooled cache arrays for every layer of one model.

    dtype: the storage mode ("int8" adds per-(layer, block) f32 scale
    arrays; "int4" additionally packs two tokens per byte along the
    block_size axis, halving the cache's HBM footprint again; anything
    else stores k/v directly). Arrays start zeroed —
    freshly (re)allocated blocks may hold stale data from a finished
    request, which is fine: reads are bounded by per-sequence lengths and
    appends overwrite before the length mask ever exposes a slot.

    THREAD CONTRACT (D15): single-owner like the engine — the ``k``/``v``
    array handles are replaced functionally by the owner thread's step
    programs through :meth:`swap` (the one sanctioned python-side
    mutation point, contract-checked); the driving engine shares its
    ThreadContract here."""

    #: D15 static marker: methods the single-owner contract guards
    _thread_contract = ("swap",)

    def __init__(self, num_layers: int, num_blocks: int, num_kv_heads: int,
                 block_size: int, head_dim: int, dtype):
        self.contract = ThreadContract("PagedKVCache")
        if int(block_size) % 8:
            raise ValueError(
                f"kv block_size {block_size} must be a multiple of 8 "
                "(sublane alignment of the (block_size, head_dim) tile)")
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.num_kv_heads = int(num_kv_heads)
        self.block_size = int(block_size)
        self.head_dim = int(head_dim)
        #: "model" | "int8" | "int4" — int4 stores int8 ARRAYS too (two
        #: tokens per byte along the block_size axis), so mode, not the
        #: array dtype, is what callers key programs/namespaces on
        self.mode = str(dtype) if str(dtype) in ("int8", "int4") else "model"
        self.quantized = self.mode != "model"
        self.dtype = jnp.int8 if self.quantized else dtype
        tok = self.block_size
        if self.mode == "int4":
            # split-half packed along the token axis: byte t holds token t
            # (low nibble) and token bs/2 + t (high nibble); block_size is
            # a multiple of 8, so the halves are exact
            tok = self.block_size // 2
        self.stored_block_size = tok
        shape = (self.num_layers, self.num_blocks, self.num_kv_heads,
                 tok, self.head_dim)
        self.k = jnp.zeros(shape, self.dtype)
        self.v = jnp.zeros(shape, self.dtype)
        if self.quantized:
            self.k_scale = jnp.full((self.num_layers, self.num_blocks),
                                    1e-8, jnp.float32)
            self.v_scale = jnp.full((self.num_layers, self.num_blocks),
                                    1e-8, jnp.float32)
        else:
            self.k_scale = self.v_scale = None

    def swap(self, k, v, k_scale=None, v_scale=None):
        """Install the updated cache buffers a step program returned —
        the only sanctioned python-side mutation of the pool handles
        (donated inputs mean the OLD handles are dead the moment the
        program ran, so a second thread racing this swap would publish
        a deleted buffer)."""
        self.contract.check("swap")
        self.k, self.v = k, v
        self.k_scale, self.v_scale = k_scale, v_scale

    @property
    def hbm_bytes(self) -> int:
        per = int(np.prod(self.k.shape)) * self.k.dtype.itemsize
        scales = 0 if self.k_scale is None else 2 * int(
            np.prod(self.k_scale.shape)) * 4
        return 2 * per + scales


class WindowRing:
    """The window layers' state of every slot: a static ring of `pages`
    cache pages a slot, in a pool of its own, position p at ring page
    `(p // block_size) mod pages`. A window layer reads the last `window`
    positions and a chunk of at most `chunk` new ones is written before
    they are read, so `ceil((window + chunk) / block_size) + 1` pages hold
    everything visible however long the sequence grows (the + 1: a span
    of that many positions may straddle one page boundary more).

    Nothing is allocated or freed: a slot's ring comes with the slot, and
    what a finished request left in it is never visible to the next one
    (below its first live position, or past its length, in the view the
    programs are handed). The programs know nothing of rings: each tick
    the host hands them `view()`, the ring rotated into logical order, and
    they address it like any block table, by position less
    `base_page * block_size`.

    Block 0 of the pool is the trash block, as in the full layers' pool;
    slot s owns blocks `1 + s * pages ...`."""

    def __init__(self, slots: int, window: int, chunk: int,
                 block_size: int):
        self.slots, self.window = int(slots), int(window)
        self.block_size = int(block_size)
        self.pages = blocks_for(int(window) + int(chunk), block_size) + 1
        self.num_blocks = 1 + self.slots * self.pages
        self._order = np.arange(self.pages, dtype=np.int64)

    def view(self, slot: int, last_pos: int):
        """(table row [pages] int32, base_page): the slot's ring in
        logical order for a step whose LAST written position is
        `last_pos`. Entry i is the block of absolute page `base_page +
        i`; the pages that end at `last_pos`'s are the newest."""
        base = max(0, int(last_pos) // self.block_size - self.pages + 1)
        row = 1 + int(slot) * self.pages + (base + self._order) % self.pages
        return row.astype(np.int32), base

    def tokens_reserved(self) -> int:
        """Positions a slot's ring holds, a layer."""
        return self.pages * self.block_size


#: the kinds of layer state `LayeredKVCache` keeps (the block modules'
#: `cache_kind` names: `parallel_block.FULL` / `SLIDING`,
#: `latent_block.LATENT`, `gated_delta_block.RECURRENT`)
FULL, WINDOW, LATENT, RECURRENT = ("full_attention", "sliding_attention",
                                   "latent", "recurrent")


class LayeredKVCache:
    """Pools for a model that declares its layers one by one: one array a
    layer (no stacked layer axis: each layer's pool is its own donated
    buffer, written in place by its layer's scatter and read by its
    layer's kernel without a slice of a stacked array being copied out
    and back) and, where the kind has one, a second (`v[i]`, else None).
    Four kinds of layer state, one a layer (`kinds`):

      full       `k`, `v` `[N, H_kv, block_size, D]`, `full_blocks` blocks
                 addressed through the `BlockAllocator`'s tables;
      window     the same arrays with the `WindowRing`'s blocks;
      latent     `k` `[N, 1, block_size, W]` in the allocator's blocks: the
                 latent and the rotary key of a position side by side in
                 one row, no heads and no V; `head_dim` is the row's
                 width `W`, the model's `rkv + dr` rounded up to whole
                 lanes (`latent_row_width`), so that a page is a
                 lane-aligned tile the decode kernel copies whole. With
                 `H_kv` = 1 the row functions below serve it unchanged;
      recurrent  a fixed-size state a SLOT, not pages: `k` `[slots,
                 *state_shape]` float32 (a linear-attention layer's
                 matrix a value head) and `v` `[slots, *conv_shape]`
                 float32 (its causal conv's last inputs, as the layer's
                 float32 projection gave them).
                 `slots` is the engine's slots + 1: the last is the trash
                 slot that a bucket's rows without a request write.

    THREAD CONTRACT (D15): single-owner like `PagedKVCache`; `swap` is the
    one sanctioned mutation point."""

    _thread_contract = ("swap",)

    def __init__(self, kinds, full_blocks: int, window_blocks: int,
                 num_kv_heads: int, block_size: int, head_dim: int, dtype,
                 slots: int = 0, state_shape=(), conv_shape=()):
        self.contract = ThreadContract("LayeredKVCache")
        if int(block_size) % 8:
            raise ValueError(
                f"kv block_size {block_size} must be a multiple of 8 "
                "(sublane alignment of the (block_size, head_dim) tile)")
        self.kinds = tuple(kinds)
        unknown = set(self.kinds) - {FULL, WINDOW, LATENT, RECURRENT}
        if unknown:
            raise ValueError(f"unknown kinds of layer state {unknown}")
        #: per layer: True where the layer keeps a window only
        self.sliding = tuple(k == WINDOW for k in self.kinds)
        if LATENT in self.kinds and int(num_kv_heads) != 1:
            raise ValueError("a latent pool has one row a position")

        def pages(n):
            return jnp.zeros((int(n), int(num_kv_heads), int(block_size),
                              int(head_dim)), dtype)

        def arrays(kind):
            if kind == RECURRENT:
                return (jnp.zeros((int(slots),) + tuple(state_shape),
                                  jnp.float32),
                        jnp.zeros((int(slots),) + tuple(conv_shape),
                                  jnp.float32))
            n = window_blocks if kind == WINDOW else full_blocks
            return pages(n), None if kind == LATENT else pages(n)

        self.k, self.v = (tuple(a) for a in zip(*map(arrays, self.kinds)))

    def swap(self, k, v):
        self.contract.check("swap")
        self.k, self.v = tuple(k), tuple(v)

    def bytes_per_token(self, is_sliding: bool) -> int:
        """Bytes one position takes in all window layers (`is_sliding`)
        or all full-history ones: K and V, or a latent pool's one row (as
        stored: its padding is held too)."""
        want = (WINDOW,) if is_sliding else (FULL, LATENT)
        return sum(a.shape[1] * a.shape[3] * a.dtype.itemsize
                   for kind, k, v in zip(self.kinds, self.k, self.v)
                   if kind in want for a in (k, v) if a is not None)

    def state_bytes_per_slot(self) -> int:
        """Bytes a slot's recurrent state takes over all recurrent
        layers (the matrix and the conv's inputs)."""
        return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for kind, k, v in zip(self.kinds, self.k, self.v)
                   if kind == RECURRENT for a in (k, v))

    @property
    def hbm_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self.k + self.v if a is not None)


def latent_row_width(width: int) -> int:
    """The stored width of a latent pool's row: `width` values rounded up
    to whole 128-lane tiles (576 -> 640: 1,280 B a position in bfloat16
    where the model's own values are 1,152)."""
    return -(-int(width) // 128) * 128


# ---------------------------------------------------- in-program updates
# All functions below are pure jnp and run inside the compiled step
# programs. Unless a docstring says "stacked", `cache`/`scale` arguments
# are ONE pool `[num_blocks, H_kv, block_size, D]` / `[num_blocks]`
# addressed by block id. That is a layer's own pool (inference/layered.py)
# or, in the stacked programs (inference/engine.py), the WHOLE stacked
# pool seen as `[L * num_blocks, ...]` (`flat_pool`) with layer l's ids
# and table entries offset by `l * num_blocks`: the pool rides the layer
# scan as a carry and is never sliced, so every write below lands in the
# donated buffer in place. Layer l's trash block is then `l * num_blocks`;
# a write these functions mask themselves goes to flat block 0, layer 0's
# trash block. Either is never read.

def flat_pool(a):
    """A stacked pool `[L, N, ...]` (or its `[L, N]` scales) seen as
    `[L * N, ...]`: a free reshape, the `(block_size, D)` tile is
    untouched."""
    return a.reshape((-1,) + a.shape[2:])


def stacked_block_ids(cache, ids):
    """Flat ids `[L * P]` (layer-major) of blocks `ids` [P] in every layer
    of the stacked `cache` `[L, N, ...]`."""
    l, n = cache.shape[:2]
    return (jnp.arange(l, dtype=jnp.int32)[:, None] * n
            + ids.astype(jnp.int32)[None, :]).reshape(-1)


def _set_blocks(cache, ids, tiles):
    """Stacked `cache` [L, N, ...] with blocks `ids` [P] of every layer
    set to `tiles` [L, P, ...]: ONE scatter along the major dimension of
    the flat view, so the donated pool is updated in place
    (`cache.at[:, ids].set(tiles)` scatters dimension 1 and copies the
    pool around it)."""
    flat = flat_pool(cache).at[stacked_block_ids(cache, ids)].set(
        flat_pool(tiles).astype(cache.dtype))
    return flat.reshape(cache.shape)


def copy_block(cache, src, dst):
    """Copy-on-write on a stacked `cache` [L, N, ...] (or its scales):
    block `src` of every layer duplicated into block `dst` (both
    TRASH_BLOCK = a no-op that rewrites each layer's trash block)."""
    flat = flat_pool(cache)
    tiles = flat[stacked_block_ids(cache, jnp.reshape(src, (1,)))]
    return flat.at[stacked_block_ids(cache, jnp.reshape(dst, (1,)))].set(
        tiles).reshape(cache.shape)


def append_token_int8(cache, scale, kv, block_ids, offsets):
    """Int8 append with per-block requantization: the touched block is
    dequantized against its current scale, the new token inserted, a new
    scale taken over the VALID prefix (slots <= offset — stale tail
    entries never pollute it), and the whole block requantized. Returns
    (cache, scale)."""
    b = kv.shape[0]
    bs = cache.shape[2]
    old = cache[block_ids].astype(jnp.float32)          # [B, Hkv, bs, D]
    x = old * scale[block_ids][:, None, None, None]
    x = x.at[jnp.arange(b), :, offsets].set(kv.astype(jnp.float32))
    valid = (jnp.arange(bs)[None, :] <= offsets[:, None])  # [B, bs]
    amax = jnp.max(jnp.abs(x) * valid[:, None, :, None], axis=(1, 2, 3))
    new_scale = jnp.maximum(amax / 127.0, 1e-8)          # [B]
    q8 = jnp.clip(jnp.round(x / new_scale[:, None, None, None]),
                  -127, 127).astype(jnp.int8)
    return (cache.at[block_ids].set(q8),
            scale.at[block_ids].set(new_scale))


def _prefill_pages(ks, true_len, table_row, block_size):
    """Shared prefill-scatter prep: ks [L, S, H_kv, D] (S a multiple of
    block_size) -> per-page tiles [L, P_b, H_kv, bs, D] plus destination
    block ids [P_b] (invalid pages -> trash) and a per-token validity
    mask [P_b, bs]."""
    l, s, hkv, d = ks.shape
    bs = int(block_size)
    p_b = s // bs
    tiles = jnp.swapaxes(ks.reshape(l, p_b, bs, hkv, d), 2, 3)
    page_valid = (jnp.arange(p_b) * bs) < true_len
    dest = jnp.where(page_valid, table_row[:p_b], TRASH_BLOCK)
    tok_valid = (jnp.arange(p_b)[:, None] * bs
                 + jnp.arange(bs)[None, :]) < true_len   # [P_b, bs]
    return tiles, dest.astype(jnp.int32), tok_valid


def scatter_prefill(cache, ks, true_len, table_row, block_size):
    """Write a whole prompt's K (or V) into its pages in one scatter.
    Stacked cache [L, N, H_kv, bs, D], ks [L, S, H_kv, D]; positions >=
    true_len land in each layer's trash block."""
    tiles, dest, _ = _prefill_pages(ks, true_len, table_row, block_size)
    return _set_blocks(cache, dest, tiles)


def scatter_prefill_int8(cache, scale, ks, true_len, table_row,
                         block_size):
    """Int8 prefill scatter (stacked cache and scale): one scale per
    (layer, page) over the page's valid tokens, whole-page requantized
    write. Returns (cache, scale)."""
    tiles, dest, tok_valid = _prefill_pages(ks, true_len, table_row,
                                            block_size)
    tf = tiles.astype(jnp.float32)                 # [L, P_b, Hkv, bs, D]
    amax = jnp.max(jnp.abs(tf) * tok_valid[None, :, None, :, None],
                   axis=(2, 3, 4))                 # [L, P_b]
    new_scale = jnp.maximum(amax / 127.0, 1e-8)
    q8 = jnp.clip(jnp.round(tf / new_scale[:, :, None, None, None]),
                  -127, 127).astype(jnp.int8)
    return (_set_blocks(cache, dest, q8),
            _set_blocks(scale, dest, new_scale))


# ------------------------------------------------ chunked-prefill updates
# One pool addressed by block id, like the decode appends above — these
# run inside the chunk-prefill program's layer scan. Unlike scatter_prefill
# the chunk's first position is NOT page-aligned (a prefix-cache hit can
# start a suffix mid-block after copy-on-write), so the scatter is
# token-granular: position p lands at (table_row[p // bs], p % bs). The
# float pools' form is `scatter_chunk_rows`, further down.

def scatter_chunk_int8(cache, scale, ks, start, true_end, table_row,
                       block_size):
    """Int8 chunk scatter: every page the chunk touches is dequantized
    against its current scale (pre-existing content — earlier chunks, a
    copy-on-write prefix — survives), the chunk tokens inserted, and the
    page requantized over its valid prefix (positions < true_end).
    Returns (cache, scale)."""
    c = ks.shape[0]
    bs = int(block_size)
    # a chunk starting mid-block spans up to ceil(c/bs)+1 pages (worst
    # case: start offset bs-1) — c//bs+1 under-counts whenever c % bs
    # and the spilled tokens would silently route to the drop index
    p_t = -(-c // bs) + 1                      # pages a C-chunk can span
    page0 = start // bs
    pages = page0 + jnp.arange(p_t)
    page_ok = (pages * bs < true_end) & (pages < table_row.shape[0])
    dest = jnp.where(page_ok,
                     table_row[jnp.clip(pages, 0, table_row.shape[0] - 1)],
                     TRASH_BLOCK).astype(jnp.int32)
    old = cache[dest].astype(jnp.float32) \
        * scale[dest][:, None, None, None]     # [P_t, Hkv, bs, D]
    pos = start + jnp.arange(c)
    ok = pos < true_end
    tok_page = jnp.where(ok, pos // bs - page0, p_t)   # OOB -> dropped
    off = (pos % bs).astype(jnp.int32)
    old = old.at[tok_page, :, off].set(ks.astype(jnp.float32),
                                       mode="drop")
    valid = (pages[:, None] * bs + jnp.arange(bs)[None, :]) < true_end
    amax = jnp.max(jnp.abs(old) * valid[:, None, :, None], axis=(1, 2, 3))
    new_scale = jnp.maximum(amax / 127.0, 1e-8)        # [P_t]
    q8 = jnp.clip(jnp.round(old / new_scale[:, None, None, None]),
                  -127, 127).astype(jnp.int8)
    return (cache.at[dest].set(q8), scale.at[dest].set(new_scale))


# -------------------------------------------------------- int4-KV updates
# Same contracts as the int8 variants above, with the block's tokens stored
# two-per-byte along the block_size axis (split-half: byte t holds token t
# in the low nibble, token bs/2 + t in the high nibble — ops/quantized's
# axis-generic rule). Every update dequantizes the touched block (unpack +
# scale), edits at FULL block_size resolution, requantizes over the valid
# prefix against the -7..7 range, and repacks — so a block's scale always
# covers exactly its valid tokens, like int8.

def _unpack_block(packed, bs):
    """[..., bs/2, D] packed int8 -> [..., bs, D] int4 values (int8)."""
    return int4_unpack(packed, bs, axis=-2)


def _requant_pack_int4(x, new_scale, lead_dims):
    """Quantize a dequantized block tensor x [..., bs, D] against
    per-block scales (broadcast over `lead_dims` leading axes) and repack
    to [..., bs/2, D] int8."""
    s = new_scale.reshape(new_scale.shape + (1,) * (x.ndim - lead_dims))
    q = jnp.clip(jnp.round(x / s), -INT4_QMAX, INT4_QMAX).astype(jnp.int8)
    return int4_pack(q, axis=-2)


def append_token_int4(cache, scale, kv, block_ids, offsets):
    """Int4 decode append: dequantize (unpack + scale) the touched block,
    insert the new token, rescale over the valid prefix, requantize and
    REPACK. cache [N, Hkv, bs/2, D] int8-packed; returns (cache, scale)."""
    b = kv.shape[0]
    bs = cache.shape[2] * 2
    old = _unpack_block(cache[block_ids], bs).astype(jnp.float32)
    x = old * scale[block_ids][:, None, None, None]     # [B, Hkv, bs, D]
    x = x.at[jnp.arange(b), :, offsets].set(kv.astype(jnp.float32))
    valid = (jnp.arange(bs)[None, :] <= offsets[:, None])  # [B, bs]
    amax = jnp.max(jnp.abs(x) * valid[:, None, :, None], axis=(1, 2, 3))
    new_scale = jnp.maximum(amax / INT4_QMAX, 1e-8)      # [B]
    packed = _requant_pack_int4(x, new_scale, 1)
    return (cache.at[block_ids].set(packed),
            scale.at[block_ids].set(new_scale))


def scatter_prefill_int4(cache, scale, ks, true_len, table_row,
                         block_size):
    """Int4 prefill scatter (stacked cache and scale): one scale per
    (layer, page) over the page's valid tokens, whole-page requantized +
    packed write. Returns (cache, scale)."""
    tiles, dest, tok_valid = _prefill_pages(ks, true_len, table_row,
                                            block_size)
    tf = tiles.astype(jnp.float32)                 # [L, P_b, Hkv, bs, D]
    amax = jnp.max(jnp.abs(tf) * tok_valid[None, :, None, :, None],
                   axis=(2, 3, 4))                 # [L, P_b]
    new_scale = jnp.maximum(amax / INT4_QMAX, 1e-8)
    packed = _requant_pack_int4(tf, new_scale, 2)
    return (_set_blocks(cache, dest, packed),
            _set_blocks(scale, dest, new_scale))


def scatter_chunk_int4(cache, scale, ks, start, true_end, table_row,
                       block_size):
    """Int4 chunk scatter: every page the chunk touches is dequantized
    (unpack + scale — pre-existing content survives), the chunk tokens
    inserted at full resolution, and the page requantized over its valid
    prefix and repacked. Same page window as int8: a chunk starting
    mid-block spans up to ceil(c/bs)+1 pages. Returns (cache, scale)."""
    c = ks.shape[0]
    bs = int(block_size)
    p_t = -(-c // bs) + 1                      # pages a C-chunk can span
    page0 = start // bs
    pages = page0 + jnp.arange(p_t)
    page_ok = (pages * bs < true_end) & (pages < table_row.shape[0])
    dest = jnp.where(page_ok,
                     table_row[jnp.clip(pages, 0, table_row.shape[0] - 1)],
                     TRASH_BLOCK).astype(jnp.int32)
    old = _unpack_block(cache[dest], bs).astype(jnp.float32) \
        * scale[dest][:, None, None, None]     # [P_t, Hkv, bs, D]
    pos = start + jnp.arange(c)
    ok = pos < true_end
    tok_page = jnp.where(ok, pos // bs - page0, p_t)   # OOB -> dropped
    off = (pos % bs).astype(jnp.int32)
    old = old.at[tok_page, :, off].set(ks.astype(jnp.float32),
                                       mode="drop")
    valid = (pages[:, None] * bs + jnp.arange(bs)[None, :]) < true_end
    amax = jnp.max(jnp.abs(old) * valid[:, None, :, None], axis=(1, 2, 3))
    new_scale = jnp.maximum(amax / INT4_QMAX, 1e-8)    # [P_t]
    packed = _requant_pack_int4(old, new_scale, 1)
    return (cache.at[dest].set(packed), scale.at[dest].set(new_scale))


# ------------------------------------------------- float pools: by rows
# A float pool's token-granular writes, as a scatter of whole [D] rows
# into the pool seen as [N * H_kv * block_size, D]. The reshape is free
# (the (block_size, D) tile is untouched) and the one scattered dimension
# is the major one, so XLA updates the donated pool in place; the form
# these replaced, `cache.at[blk, :, off].set(kv)`, scatters dimensions 0
# and 2 of the 4-D array and made the chip's compiler copy the whole pool
# into another layout and back around it (PERF.md, PR 31 and PR 32). Every
# step program writes a float pool through these.

def _pool_rows(cache, blocks, offsets):
    """Flat row index [T, H_kv] of (blocks[t], h, offsets[t])."""
    _, hkv, bs, _ = cache.shape
    return ((blocks[:, None] * hkv + jnp.arange(hkv)[None, :]) * bs
            + offsets[:, None])


def _set_rows(cache, rows, kv):
    n, hkv, bs, d = cache.shape
    flat = cache.reshape(n * hkv * bs, d)
    flat = flat.at[rows.reshape(-1)].set(
        kv.reshape(-1, d).astype(cache.dtype))
    return flat.reshape(n, hkv, bs, d)


def append_rows(cache, kv, block_ids, offsets):
    """Scatter one token per slot: kv [B, H_kv, D] written at
    (block_ids[b], :, offsets[b]). Padded slots route block_ids to a
    trash block; duplicate trash destinations are harmless."""
    return _set_rows(cache, _pool_rows(cache, block_ids, offsets), kv)


def scatter_chunk_rows(cache, ks, start, true_end, table_row, block_size):
    """Write one chunk's K (or V) through the block table. ks [C, H_kv, D]
    holds positions [start, start + C); positions >= true_end route to
    the trash block.

    Speculative verify windows (round 16) reuse this scatter with
    chunk = K+1 candidate tokens. Rollback of rejected candidates is
    NOT an erase: the host simply does not advance the slot's kv_len
    past the accepted prefix, so the stale-data contract above makes
    the rejected K/V unreachable (length masks bound every read), and
    the next window idempotently overwrites the same positions."""
    pos = start + jnp.arange(ks.shape[0])
    page = jnp.clip(pos // block_size, 0, table_row.shape[0] - 1)
    blk = jnp.where(pos < true_end, table_row[page], TRASH_BLOCK)
    off = (pos % block_size).astype(jnp.int32)
    return _set_rows(cache, _pool_rows(cache, blk, off), ks)


def gather_context(cache, scale, table_row, ctx_pages, int4=False):
    """One layer's context K (or V) for chunk attention: the first
    `ctx_pages` table entries gathered to [ctx_pages * bs, H_kv, D]
    (dequantized when `scale` is given; `int4=True` additionally unpacks
    the token axis first). Unwritten/trash pages surface garbage that the
    caller's `kv_pos <= q_pos` mask never attends."""
    tiles = cache[table_row[:ctx_pages]]       # [P, Hkv, bs(/2), D]
    if int4:
        tiles = _unpack_block(tiles, tiles.shape[2] * 2)
    if scale is not None:
        tiles = tiles.astype(jnp.float32) \
            * scale[table_row[:ctx_pages]][:, None, None, None]
    p, hkv, bs, d = tiles.shape
    return jnp.swapaxes(tiles, 1, 2).reshape(p * bs, hkv, d)

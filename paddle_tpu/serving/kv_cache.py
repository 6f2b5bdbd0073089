"""The paged KV cache of continuous batching.

:class:`PagedKVCache` holds a fixed ``[n_layers, n_blocks, block_size,
*line]`` pool (two, K and V, of ``line = (kv, hd)``; one where a line
holds all that is kept of a position: a latent and its shared rotary key)
plus host-side per-slot block tables (numpy int32). Slots
draw fixed-size blocks on demand, so a request only ever holds
``ceil(len/block_size)`` blocks and not worst-case ``max_len`` lines,
and requests sharing a system prompt share the full blocks of that
prefix through a refcounted radix index (:class:`RadixIndex`) —
copy-on-write on the partial tail block (the sharer recomputes the tail
into a private block; full blocks alias). Shapes stay fixed (the pool
and the ``[n_slots, max_blocks]`` tables are static-shape jit operands),
so the compiled-program count does not grow with the traffic.

Block 0 is a reserved TRASH block: it is never allocated, and in-program
scatter writes that must not land anywhere real (bucket padding, shared
prefix positions, inactive decode rows) are redirected into it — the
causal bound keeps it unreadable, so masked writes cost no extra program.

The device buffers are threaded functionally through the engine's jitted
prefill/decode programs (these objects just hold the latest arrays); the
allocators, block tables and position mirrors live host-side in numpy so
engine bookkeeping never dispatches device ops between steps.
"""
from __future__ import annotations

import collections
import heapq

import numpy as np

TRASH_BLOCK = 0   # reserved scatter target for masked writes, never allocated


# ---------------------------------------------------------------------------
# paged pool
# ---------------------------------------------------------------------------


class BlockPool:
    """Refcounted fixed-size block allocator (host-side, ids only).

    Block 0 is the reserved trash block and is never handed out; a
    block's refcount counts every holder — each slot referencing it plus
    the radix index if it holds the block for reuse. ``deref`` returns
    the block to the free list when the count reaches zero.
    """

    def __init__(self, n_blocks):
        if n_blocks < 2:
            raise ValueError("n_blocks must be >= 2 (block 0 is trash)")
        self.n_blocks = int(n_blocks)
        self.refcount = np.zeros(self.n_blocks, np.int32)
        self.refcount[TRASH_BLOCK] = 1       # pinned forever
        self._free = collections.deque(range(1, self.n_blocks))

    @property
    def n_free(self):
        return len(self._free)

    @property
    def n_used(self):
        return self.n_blocks - 1 - len(self._free)

    def alloc(self):
        """Claim a free block at refcount 1, or None when exhausted."""
        if not self._free:
            return None
        b = self._free.popleft()
        self.refcount[b] = 1
        return b

    def ref(self, b):
        if self.refcount[b] < 1:
            raise ValueError(f"block {b} is not allocated")
        self.refcount[b] += 1

    def deref(self, b):
        if b == TRASH_BLOCK:
            return
        if self.refcount[b] < 1:
            raise ValueError(f"block {b} double-freed")
        self.refcount[b] -= 1
        if self.refcount[b] == 0:
            self._free.append(b)


class _RadixNode:
    __slots__ = ("children", "block", "parent", "key")

    def __init__(self, parent=None, key=None, block=None):
        self.children = {}          # chunk bytes -> _RadixNode
        self.parent = parent
        self.key = key
        self.block = block


class RadixIndex:
    """Prefix trie over full-block token chunks -> pool block ids.

    Each node below the root owns exactly one full block of prompt
    tokens (keyed by the chunk's byte content — exact tokens, no hash
    collisions) and holds one pool reference on that block, so a prefix
    stays resident for reuse after its producing request finishes.
    ``match`` returns the longest already-cached full-chunk prefix;
    ``evict`` reclaims leaf blocks nobody but the index references when
    the pool runs dry (newest-inserted leaves last: old shared system
    prompts survive churn).
    """

    def __init__(self, block_size):
        self.block_size = int(block_size)
        self.root = _RadixNode()
        self.n_nodes = 0
        self._clock = 0
        self._touch = {}            # node -> last-use tick (LRU eviction)
        self._blocks = set()        # the pool blocks the nodes hold

    def _chunks(self, tokens):
        bs = self.block_size
        t = np.asarray(tokens, np.int32)
        for i in range(len(t) // bs):
            yield t[i * bs:(i + 1) * bs].tobytes()

    def match(self, tokens):
        """Longest cached full-block prefix of ``tokens`` -> block ids
        (in prefix order). Does NOT take pool references — callers ref
        the returned blocks while the radix lock on them still holds."""
        node = self.root
        out = []
        self._clock += 1
        for key in self._chunks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            out.append(child.block)
            self._touch[child] = self._clock
            node = child
        return out

    def match_len(self, tokens):
        """Length in TOKENS of the longest cached full-block prefix of
        ``tokens`` — a read-only probe (no LRU touch, no pool refs) for
        the fleet router's prefix-affinity signal: probing every replica
        per admission must not perturb any replica's eviction order."""
        node = self.root
        n = 0
        for key in self._chunks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            n += self.block_size
            node = child
        return n

    def insert(self, tokens, block_ids, pool):
        """Register ``tokens``' full blocks (already written to
        ``block_ids``, one per full chunk) for future sharing. Chunks
        already present keep their existing block (the caller's private
        copy of that chunk stays owned by its slot alone); each newly
        inserted node takes one pool reference on its block."""
        node = self.root
        self._clock += 1
        inserted = 0
        for key, b in zip(self._chunks(tokens), block_ids):
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(parent=node, key=key, block=int(b))
                node.children[key] = child
                pool.ref(child.block)
                self._blocks.add(child.block)
                self.n_nodes += 1
                inserted += 1
            self._touch[child] = self._clock
            node = child
        return inserted

    def _leaves(self):
        stack = [self.root]
        while stack:
            n = stack.pop()
            if n is not self.root and not n.children:
                yield n
            stack.extend(n.children.values())

    def evictable_blocks(self, pool):
        """Number of index-held blocks reclaimable right now (leaf
        chain): blocks only the index references. Asked before every
        admission pass, so it reads the pool's refcounts at the index's
        blocks in one numpy pass instead of walking the trie."""
        if not self._blocks:
            return 0
        held = np.fromiter(self._blocks, np.int64, len(self._blocks))
        return int(np.count_nonzero(pool.refcount[held] == 1))

    def _nodes(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    def evict(self, pool, need=1):
        """Drop least-recently-matched leaves whose block nobody else
        references until ``need`` blocks are freed (or no progress).
        Returns the number of blocks actually freed. ONE walk of the
        trie a call: the evictable leaves go on a heap by last use, and
        a parent whose last child went joins it (walking every leaf anew
        for each block made an admission of a 2.5 k-token prompt into a
        full pool of 8 k blocks take 93 ms on the chip's host, PR 27)."""
        heap = [(self._touch.get(n, 0), i, n)
                for i, n in enumerate(self._leaves())
                if pool.refcount[n.block] == 1]
        heapq.heapify(heap)
        order, freed = len(heap), 0
        while freed < need and heap:
            _, _, victim = heapq.heappop(heap)
            parent = victim.parent
            pool.deref(victim.block)
            del parent.children[victim.key]
            self._touch.pop(victim, None)
            self._blocks.discard(victim.block)
            self.n_nodes -= 1
            freed += 1
            if parent is not self.root and not parent.children \
                    and pool.refcount[parent.block] == 1:
                heapq.heappush(heap, (self._touch.get(parent, 0), order,
                                      parent))
                order += 1
        return freed

    def clear(self, pool):
        for n in self._nodes():
            pool.deref(n.block)
        self.root = _RadixNode()
        self.n_nodes = 0
        self._touch = {}
        self._blocks = set()


class PagedKVCache:
    """Paged KV pool + host-side slot/block bookkeeping.

    A slot-level surface (``alloc``/``free``/``active``/``cur_pos``/
    ``n_free``/``occupancy``) for the engine and the supervisor, and
    under it the block tables (a static-shape ``[n_slots, max_blocks]``
    int32 jit operand), the refcounted pool and the radix prefix index.

    ``line`` is the shape of what one position keeps in one layer's pool,
    ``(kv_heads, head_dim)``; ``values`` says whether a second pool of
    the same shape holds V beside K. A latent cache has ``values=False``:
    ``vc`` is None and one line is all there is of a position. Blocks,
    tables and the index count lines, whatever their shape.

    ``n_layers`` counts the layers whose lines are kept WHOLE: the pool
    spans those alone. Two further kinds of state are sized apart and held
    in ``state`` (None where a model has neither), a dict of arrays the
    engine threads through its programs beside the pool: ``window =
    (layers, lines)``, for layers that read no further back than ``lines``
    positions, gives ``wk`` / ``wv`` ``[layers, 1 + n_slots * lines /
    block_size, block_size, *line]``, a ring of ``lines`` a slot behind a
    constant block table (block 0 is trash), so such a layer never holds
    more than its window; ``recurrent = {name: (layers, shape, dtype)}``
    gives ``[layers, n_slots, *shape]`` of what a recurrent layer carries
    from token to token. ``folded`` holds every block as ``[block_size *
    kv_heads, head_dim]``. Neither is allocated by blocks: a slot owns its
    share while it is occupied, and the program that first writes a
    prompt's rows resets it.
    """

    def __init__(self, n_layers, n_slots, max_len, line, dtype,
                 block_size=16, n_blocks=None, values=True, window=None,
                 recurrent=None, folded=False):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if max_len < 2:
            raise ValueError("max_len must be >= 2")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.n_layers = int(n_layers)
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.line = tuple(int(n) for n in line)
        self.kv_heads, self.head_dim = self.line
        self.values = bool(values)
        self.dtype = np.dtype(dtype)
        self.block_size = int(block_size)
        self.max_blocks = -(-self.max_len // self.block_size)
        if n_blocks is None:
            # worst case, every slot at max_len (+ trash): the pool can
            # never run dry; size it DOWN explicitly to bank the memory
            # win
            n_blocks = self.n_slots * self.max_blocks + 1
        self.pool = BlockPool(n_blocks)
        self.radix = RadixIndex(self.block_size)
        self.folded = bool(folded)
        shape = (self.n_layers, self.pool.n_blocks) \
            + self._block(self.block_size)
        # plain numpy zeros: first jit call device-puts them (no compile)
        self.kc = np.zeros(shape, self.dtype)
        self.vc = np.zeros(shape, self.dtype) if self.values else None
        self.window = None if window is None else tuple(map(int, window))
        self.state = None
        if window is not None or recurrent:
            self.state = {}
        if window is not None:
            layers, lines = self.window
            if lines % self.block_size:
                raise ValueError(f"a window of {lines} lines is no whole "
                                 f"number of blocks of {self.block_size}")
            ring = (layers, 1 + self.n_slots * lines // self.block_size) \
                + self._block(self.block_size)
            self.state["wk"] = np.zeros(ring, self.dtype)
            self.state["wv"] = np.zeros(ring, self.dtype)
        for name, (layers, per_slot, dt) in (recurrent or {}).items():
            self.state[name] = np.zeros(
                (int(layers), self.n_slots) + tuple(per_slot), np.dtype(dt))
        self.block_tables = np.zeros((self.n_slots, self.max_blocks),
                                     np.int32)      # 0 = trash/unused
        self.cur_pos = np.zeros(self.n_slots, np.int32)
        self.active = np.zeros(self.n_slots, bool)
        self._free = collections.deque(range(self.n_slots))
        self._owner = [None] * self.n_slots
        self._slot_blocks = [[] for _ in range(self.n_slots)]
        self._slot_shared = np.zeros(self.n_slots, np.int32)  # blocks
        # pool telemetry for serving metrics
        self.low_watermark = self.pool.n_free

    def _block(self, lines):
        """The shape of a block of ``lines`` lines: ``(lines, kv, hd)``,
        or ``folded`` to ``(lines * kv, hd)``, lines and heads one run of
        rows (the same bytes; what a pool whose heads fill no whole tile
        of the chip is held as, ``text/sambay.py::_scatter`` says why)."""
        if self.folded:
            return (lines * self.kv_heads, self.head_dim)
        return (lines,) + self.line

    # -- slot surface ------------------------------------------------------

    @property
    def n_free(self):
        return len(self._free)

    @property
    def n_active(self):
        return int(self.active.sum())

    @property
    def occupancy(self):
        return self.n_active / self.n_slots

    def alloc(self, request_id=None):
        if not self._free:
            return None
        slot = self._free.popleft()
        self.active[slot] = True
        self.cur_pos[slot] = 0
        self._owner[slot] = request_id
        return slot

    def free(self, slot):
        """Evict a slot: every block reference it holds (shared prefix
        AND private tail/decode blocks) is dropped; blocks the radix
        still indexes stay resident for future sharers, the rest return
        to the pool. Device lines are NOT cleared — a freed block is
        only re-read after a later occupant overwrites it (causal
        bound + table ordering)."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self.active[slot] = False
        self._owner[slot] = None
        for b in self._slot_blocks[slot]:
            self.pool.deref(b)
        self._slot_blocks[slot] = []
        self._slot_shared[slot] = 0
        self.block_tables[slot, :] = TRASH_BLOCK
        self._free.append(slot)

    def owner(self, slot):
        return self._owner[slot]

    def bytes_by_kind(self):
        """What is held, by kind of state: ``pool_bytes`` (the paged
        pools, padding of a line included), ``window_bytes`` (the rings of
        the window layers, their trash blocks included) and
        ``state_bytes`` (what the recurrent layers carry)."""
        held = {k: int(np.prod(a.shape)) * a.dtype.itemsize
                for k, a in (self.state or {}).items()}
        ring = held.pop("wk", 0) + held.pop("wv", 0)
        return {"pool_bytes": (1 + self.values) * self.n_layers
                * self.pool.n_blocks * self.block_size * self.kv_heads
                * self.head_dim * self.dtype.itemsize,
                "window_bytes": ring, "state_bytes": sum(held.values())}

    def nbytes(self):
        """What the cache really holds, of every kind."""
        return sum(self.bytes_by_kind().values())

    # -- paged admission ---------------------------------------------------

    def free_tokens(self, include_evictable=True):
        """Admission headroom in token lines: free blocks plus (by
        default) radix-held blocks reclaimable on demand."""
        n = self.pool.n_free
        if include_evictable:
            n += self.radix.evictable_blocks(self.pool)
        return n * self.block_size

    def _alloc_or_evict(self):
        b = self.pool.alloc()
        if b is None and self.radix.evict(self.pool, need=1):
            b = self.pool.alloc()
        if b is not None:
            self.low_watermark = min(self.low_watermark, self.pool.n_free)
        return b

    def admit(self, slot, prompt_ids, n_cover):
        """Wire slot block-table coverage for logical positions
        ``[0, n_cover)``: the longest radix-cached full-block prefix of
        ``prompt_ids`` is shared (refcounted, never written by this
        slot), the rest allocated privately. Returns
        ``(n_shared_tokens, cow_copy)`` or None when the pool cannot
        cover the request even after radix eviction (caller re-queues);
        ``cow_copy`` is True when a shared prefix ends mid-prompt so the
        partial tail block was privatized (copy-on-write recompute)."""
        assert not self._slot_blocks[slot], "slot already wired"
        shared = self.radix.match(prompt_ids)
        need_blocks = -(-int(n_cover) // self.block_size)
        shared = shared[:need_blocks]
        blocks = []
        for b in shared:
            self.pool.ref(b)
            blocks.append(b)
        # what the free list cannot cover is evicted in one call
        short = need_blocks - len(shared) - self.pool.n_free
        if short > 0:
            self.radix.evict(self.pool, need=short)
        ok = True
        for _ in range(need_blocks - len(shared)):
            b = self._alloc_or_evict()
            if b is None:
                ok = False
                break
            blocks.append(b)
        if not ok:
            for b in blocks:
                self.pool.deref(b)
            return None
        self._slot_blocks[slot] = blocks
        self._slot_shared[slot] = len(shared)
        self.block_tables[slot, :] = TRASH_BLOCK
        self.block_tables[slot, :len(blocks)] = blocks
        n_shared_tokens = len(shared) * self.block_size
        cow = bool(shared) and n_shared_tokens < len(prompt_ids)
        return n_shared_tokens, cow

    def ensure(self, slot, pos):
        """Guarantee a writable block exists for logical position
        ``pos`` (decode growth). True on success, False when the pool is
        exhausted (caller preempts someone)."""
        idx = int(pos) // self.block_size
        if idx < len(self._slot_blocks[slot]):
            return True
        assert idx == len(self._slot_blocks[slot]), "non-contiguous growth"
        b = self._alloc_or_evict()
        if b is None:
            return False
        self._slot_blocks[slot].append(b)
        self.block_tables[slot, idx] = b
        return True

    def commit_prefix(self, slot, prompt_ids):
        """After a slot's prefill fully completes, publish its prompt's
        full blocks into the radix index so later requests share them."""
        n_full = len(prompt_ids) // self.block_size
        return self.radix.insert(prompt_ids,
                                 self._slot_blocks[slot][:n_full],
                                 self.pool)

    def shared_tokens(self, slot):
        return int(self._slot_shared[slot]) * self.block_size

    def live_blocks(self):
        """Sorted unique block ids referenced by occupied slots (the KV
        finiteness probe walks exactly these — trash and radix-only
        blocks hold no live request state)."""
        out = set()
        for slot in range(self.n_slots):
            if self.active[slot]:
                out.update(self._slot_blocks[slot])
        out.discard(TRASH_BLOCK)
        return sorted(out)

    def shared_live_blocks(self):
        """Live blocks referenced by more than one holder (slot-shared
        prefix blocks; includes index-resident shared blocks) — the
        chaos kv-corrupt target set."""
        return [b for b in self.live_blocks()
                if self.pool.refcount[b] > 1]

    def check_refcounts(self):
        """Pool/table/radix invariant: every block's refcount equals the
        number of slots holding it plus one if the radix indexes it, and
        free-list membership is exact. Used by chaos verdicts/tests."""
        want = np.zeros(self.pool.n_blocks, np.int32)
        want[TRASH_BLOCK] = 1
        for blocks in self._slot_blocks:
            for b in blocks:
                want[b] += 1
        for n in self.radix._nodes():
            want[n.block] += 1
        if not np.array_equal(want, self.pool.refcount):
            return False
        free = set(self.pool._free)
        return all((self.pool.refcount[b] == 0) == (b in free)
                   for b in range(1, self.pool.n_blocks))

    def pool_stats(self):
        return {"n_blocks": self.pool.n_blocks,
                "block_size": self.block_size,
                "blocks_free": self.pool.n_free,
                "blocks_used": self.pool.n_used,
                "blocks_low_watermark": int(self.low_watermark),
                "radix_nodes": self.radix.n_nodes,
                "pool_occupancy_now": round(
                    self.pool.n_used / max(1, self.pool.n_blocks - 1), 4),
                **(self.bytes_by_kind() if self.state is not None else {})}

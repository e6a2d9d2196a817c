"""The block-paged cache for continuous-batching decode.

No reference counterpart (the reference delegates all inference to TF
Serving, SURVEY.md §2.2; reference Inference.scala:27-79 is offline
batch only).

What is cached belongs to the model: ``cfg.decode_fns().rows`` is a tuple
of entries (``models/transformer.CacheEntry``), each with a name, the
shape one layer keeps of one sequence, the number of layers that keep
such a thing, and a dtype (None: the cache's).  An entry whose shape has
a ``None`` is ROWS PER TOKEN, the None standing for the token axis
(per-head keys and values: ``("k", (None, heads, head_dim), n_layers)``
and ``"v"``; a latent cache: ``("kv", (None, row_width), layers)``): a
paged pool ``[num_blocks, layers, *shape]`` with ``block_size`` for the
None.  An entry without one is PER-SESSION STATE of a fixed size (a
linear-attention layer's recurrent state): ``[layers, slots, *shape]``,
row s of every layer the state of the session in slot s — written whole
when the session is admitted, owned with the slot, never paged, never in
the trie.  (Layers lead because a step rewrites one layer's rows of ALL
slots at a time: with the slots leading XLA re-lays the whole array out at
the step's entry and again at its exit, two copies of all state a step.)
This module allocates the arrays from that, reaches them as
``cache.pools`` (a tuple in the layout's order, also ``cache.<name>``),
and moves a prefill's output in: rows along the token axis by block, state
by slot; everything else — slots, block tables, refcounts, the trie —
never looks inside either.

:class:`PagedKVCache` — block paging with ref-counted prefix sharing,
the one cache there is (a speculative draft model has a second instance
without a trie): a pool is ``[num_blocks, n_layers, ...block_size...]``
and each slot maps logical positions through a per-slot block-table row
(the model's paged step gathers through it). Blocks carry refcounts, so
admission can map a new request's matched prompt-prefix blocks from the
:class:`PrefixTrie` (bumping refcounts) instead of re-prefilling them —
only the unmatched tail is prefilled, and tail writes always land in
session-private blocks because trie matches are whole-block
(copy-on-write by block alignment, never in place).  Retired sessions
decref; blocks a trie path still references stay resident for future
hits and are reclaimed LRU-leaf-first only when allocation would
otherwise fail.  A pool sized to its live set fills with such blocks
within minutes and every allocation then evicts, so the trie keeps its
eviction order as it goes (a heap of candidate leaves): releasing a
block costs a heap operation, whatever the trie holds.

Physical block 0 is a reserved SENTINEL: free slots' table rows point
at it, so their numerically-inert writes (and the padded rows of a
bucketed ``prefill_extend``) land in a block no live session ever
attends to.  Capacity is validated so live sessions can never be starved:
``num_blocks - 1 >= slots * blocks_per_slot`` and everything above the
sentinel that is not session-referenced is trie-reclaimable.

jax is imported lazily: the classes are instantiated replica-side only
(scheduler.DecodeEngine); the driver half of serving never pulls jax.
"""

from __future__ import annotations

import functools
import heapq
import time

import numpy as np

from tensorflowonspark_tpu.utils import telemetry


@functools.lru_cache(maxsize=4)
def _kv_insert(token_axes, block_size):
    """The insert as ONE named program (``jit_tfos_kv_insert``, scope
    ``kv_insert``), on the device from end to end: row ``row`` of a
    prefill's ``[B, layers, ...]`` outputs goes into the pools.  An entry
    with a token axis (``token_axes``: its place in the entry's shape,
    None for per-session state) is cut into blocks and scattered at
    ``blocks``.  ``blocks`` has one entry per block of the PADDED length T
    (the prefill's bucket), the entries past the prompt's own blocks
    naming the sentinel, so there is one program per prefill shape and
    not one per prompt length, and no row travels through the host.  A
    state entry overwrites row ``slot`` of every layer of its pool.  The
    pools are donated: the cache keeps the new ones, and a second copy of
    a pool never exists."""
    import jax
    import jax.numpy as jnp

    def tfos_kv_insert(pools, blocks, rows, row, slot=0):
        nb = blocks.shape[0]
        out = []
        with jax.named_scope("kv_insert"):
            for pool, r, ax in zip(pools, rows, token_axes):
                r = jax.lax.dynamic_index_in_dim(r, row, 0, keepdims=False)
                if ax is None:
                    out.append(pool.at[:, slot].set(r.astype(pool.dtype)))
                    continue
                ax += 1                          # after the layer axis
                pad = [(0, 0)] * r.ndim
                pad[ax] = (0, nb * block_size - r.shape[ax])
                r = jnp.pad(r, pad)
                r = r.reshape(r.shape[:ax] + (nb, block_size)
                              + r.shape[ax + 1:])
                out.append(pool.at[blocks].set(
                    jnp.moveaxis(r, ax, 0).astype(pool.dtype)))
        return tuple(out)

    return jax.jit(tfos_kv_insert, donate_argnums=(0,))


class CacheOOM(RuntimeError):
    """Block allocation failed even after trie reclamation."""


def resolve_prefix_sharing(layout, asked):
    """Whether a cache of ``layout`` keeps a prefix trie: ``asked`` (None:
    wherever the layout allows).  A layout with per-session state does
    not allow it: a matched prefix's rows can be mapped, but the state at
    the end of the prefix is not kept anywhere (nothing snapshots state
    at block boundaries yet), so the tail could not be computed."""
    has_state = any(not entry.paged for entry in layout)
    if asked and has_state:
        raise ValueError(
            "prefix_sharing=True with a cache layout that has per-session "
            "state (" + ", ".join(e.name for e in layout if not e.paged)
            + "): a matched prefix's rows could be mapped, but the state at "
            "its end is not kept, so its tail cannot be prefilled; leave "
            "prefix_sharing unset")
    return not has_state if asked is None else bool(asked)


class _TrieNode:
    __slots__ = ("children", "block", "tick", "parent", "key")

    def __init__(self, block, tick, parent, key):
        self.children = {}      # block-token tuple -> _TrieNode
        self.block = int(block)
        self.tick = tick        # None once evicted
        self.parent = parent    # None under the root
        self.key = key          # its key in the parent's ``children``


class PrefixTrie:
    """Prompt-prefix index over resident KV blocks.

    Keys are whole blocks of prompt tokens (tuples of ``block_size``
    ints), so a match is always block-aligned — the property that lets
    a matching session map the physical blocks directly (the KV of a
    prompt position depends only on the tokens at and before it, and
    keys are cached post-rope, so identical prompt blocks at identical
    positions have identical cache content).  Each node holds ONE
    refcount on its physical block (taken at insert, dropped at evict);
    session references stack on top, so ``refcount == 1`` means
    "trie-only" — the reclaimable state.

    Eviction is least-recently-touched LEAF first, and the order is
    kept as the trie changes, not found by a walk: ``_heap`` is a
    min-heap of ``(tick, seq, node)`` candidates, pushed wherever a node
    becomes a leaf or a leaf's tick changes (the end of an inserted or
    matched path, a parent whose last child was evicted) and checked
    when popped — an entry counts only while its node is in the trie,
    is a leaf and carries that tick.  Ticks are unique among leaves: one
    tick goes to the nodes of one root path, of which at most one is a
    leaf.  Reference counts change outside the trie without notice, so
    they are read at the pop too.  Entries that went stale stay until
    popped; :meth:`_bound_heap` rebuilds the heap from the live leaves
    once it holds more than ``HEAP_SLACK * nodes + HEAP_SLACK_MIN``.

    Host-side bookkeeping only; the trie never touches device arrays.
    ``reclaim_calls``, ``blocks_reclaimed`` and ``reclaim_s`` (seconds
    inside :meth:`reclaim`) are totals since the trie was made.
    """

    HEAP_SLACK = 2
    HEAP_SLACK_MIN = 64

    def __init__(self, block_size):
        self.block_size = int(block_size)
        self.root = {}          # block-token tuple -> _TrieNode
        self._tick = 0
        self.nodes = 0
        self._heap = []         # (tick, seq, node): eviction candidates
        self._seq = 0           # orders entries of one tick: nodes do not
        self.reclaim_calls = 0
        self.blocks_reclaimed = 0
        self.reclaim_s = 0.0

    def _blocks_of(self, tokens, limit=None):
        bs = self.block_size
        n = len(tokens) // bs if limit is None else min(
            len(tokens) // bs, limit)
        return [tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(n)]

    def _offer(self, node):
        """``node``, if a leaf, is an eviction candidate at its tick."""
        if node is not None and not node.children:
            self._seq += 1
            heapq.heappush(self._heap, (node.tick, self._seq, node))

    def walk(self):
        """Every node, parents before their children."""
        stack = [self.root]
        while stack:
            for node in stack.pop().values():
                yield node
                if node.children:
                    stack.append(node.children)

    def _bound_heap(self):
        """Drop the stale entries once they outnumber the live ones:
        the one walk left, paid for by the pushes that called for it."""
        if len(self._heap) <= (self.HEAP_SLACK * self.nodes
                               + self.HEAP_SLACK_MIN):
            return
        leaves = [node for node in self.walk() if not node.children]
        self._heap = [(node.tick, self._seq + i, node)
                      for i, node in enumerate(leaves, 1)]
        self._seq += len(leaves)
        heapq.heapify(self._heap)

    def match(self, tokens, limit=None):
        """Physical block ids of the longest resident whole-block
        prefix of ``tokens`` (at most ``limit`` blocks); touches the
        matched path's LRU ticks."""
        self._tick += 1
        out, children, node = [], self.root, None
        for key in self._blocks_of(tokens, limit):
            nxt = children.get(key)
            if nxt is None:
                break
            node = nxt
            node.tick = self._tick
            out.append(node.block)
            children = node.children
        self._offer(node)           # a leaf here has a new tick
        self._bound_heap()
        return out

    def insert(self, tokens, phys_blocks, incref):
        """Register ``tokens``' whole-block prefix as resident in
        ``phys_blocks`` (one id per block).  Existing nodes keep their
        own (content-identical) blocks; each NEWLY created node calls
        ``incref(block)`` to take the trie's reference."""
        self._tick += 1
        children, node = self.root, None
        for key, block in zip(self._blocks_of(tokens), phys_blocks):
            nxt = children.get(key)
            if nxt is None:
                nxt = _TrieNode(block, self._tick, node, key)
                children[key] = nxt
                self.nodes += 1
                incref(nxt.block)
            else:
                nxt.tick = self._tick
            node = nxt
            children = node.children
        self._offer(node)           # the new leaf, or an old one touched
        self._bound_heap()

    def reclaim(self, need, refcount, release):
        """Evict least-recently-matched leaf nodes whose blocks are
        trie-only (``refcount[block] == 1``) until ``need`` blocks were
        released or nothing else is evictable.  Returns the count
        released.  Evicting a leaf may expose its parent as the next
        candidate.  The cost is that of the entries popped — the blocks
        released, the stale ones, and one leaf per session that holds
        its own — whatever the trie's size."""
        t0 = time.perf_counter()
        heap, held, freed = self._heap, [], 0
        while freed < need and heap:
            entry = heapq.heappop(heap)
            tick, _seq, node = entry
            if node.tick != tick or node.children:
                continue            # evicted, touched since, or grown
            if refcount[node.block] != 1:
                held.append(entry)  # a session's: back when we are done
                continue
            parent = node.parent
            del (self.root if parent is None else parent.children)[node.key]
            node.tick = None
            self.nodes -= 1
            release(node.block)
            freed += 1
            self._offer(parent)
        for entry in held:
            heapq.heappush(heap, entry)
        self._bound_heap()
        self.reclaim_calls += 1
        self.blocks_reclaimed += freed
        self.reclaim_s += time.perf_counter() - t0
        return freed


class PagedKVCache:
    """Block-paged pools + per-slot block tables + prefix trie.

    Device side: ``pools`` in the order of the model's layout and also an
    attribute each under its entry's name: rows per token ``[num_blocks,
    the entry's layers, ...block_size...]``, per-session state ``[the
    entry's layers, slots, ...]`` (a slot's row is its session's: given
    out with the slot, overwritten whole by the session's insert).  Host
    side: ``block_tables`` [slots,
    blocks_per_slot] int32 (unused entries point at sentinel block 0),
    ``lengths`` [slots], ``refcount`` [num_blocks], a block free list and
    a slot free list.  The model's paged step and tail prefill consume
    the pools + tables directly.
    """

    def __init__(self, cfg, slots, block_size=None, num_blocks=None,
                 max_seq=None, dtype=None, prefix_sharing=None):
        import jax.numpy as jnp

        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError("need at least one slot")
        self.max_seq = int(max_seq or cfg.max_seq)
        self.block_size = int(block_size or 16)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.blocks_per_slot = -(-self.max_seq // self.block_size)
        min_blocks = 1 + self.slots * self.blocks_per_slot
        # default: 2x the live working set — the surplus is what lets
        # trie-retained prefixes of RETIRED sessions stay resident
        self.num_blocks = int(num_blocks or
                              1 + 2 * self.slots * self.blocks_per_slot)
        if self.num_blocks < min_blocks:
            raise ValueError(
                f"num_blocks {self.num_blocks} < sentinel + "
                f"slots*blocks_per_slot = {min_blocks}: live sessions "
                "could starve")
        self.layout = cfg.decode_fns().rows
        self.dtype = dtype or cfg.compute_dtype
        self._token_axes = tuple(
            entry.shape.index(None) if entry.paged else None
            for entry in self.layout)
        self.pools = tuple(
            jnp.zeros(((self.num_blocks, entry.layers) if entry.paged
                       else (entry.layers, self.slots))
                      + tuple(self.block_size if d is None else d
                              for d in entry.shape),
                      entry.dtype or self.dtype)
            for entry in self.layout)
        self.block_tables = np.zeros((self.slots, self.blocks_per_slot),
                                     np.int32)
        self.lengths = np.zeros((self.slots,), np.int32)
        self.refcount = np.zeros((self.num_blocks,), np.int64)
        self.refcount[0] = 1            # sentinel: pinned forever
        self._nblocks = np.zeros((self.slots,), np.int32)
        self._free_blocks = list(range(self.num_blocks - 1, 0, -1))
        self._free = list(range(self.slots - 1, -1, -1))
        self.trie = PrefixTrie(self.block_size) if resolve_prefix_sharing(
            self.layout, prefix_sharing) else None

    def __getattr__(self, name):
        # only reached for names not found the normal way
        layout = self.__dict__.get("layout", ())
        for i, entry in enumerate(layout):
            if entry.name == name:
                return self.pools[i]
        raise AttributeError(name)

    def _entry_bytes(self, paged):
        return sum(self.entry_row_bytes(entry.name) for entry in self.layout
                   if entry.paged == paged)

    def entry_row_bytes(self, name):
        """Bytes one token (or one session) takes in the entry ``name``,
        over the layers that have it."""
        entry, pool = next((e, p) for e, p in zip(self.layout, self.pools)
                           if e.name == name)
        return int(np.prod([d for d in entry.shape if d is not None])) \
            * entry.layers * pool.dtype.itemsize

    @property
    def row_bytes(self):
        """Bytes one cached TOKEN takes: the paged entries, each over the
        layers that have it."""
        return self._entry_bytes(True)

    @property
    def state_row_bytes(self):
        """Bytes one SESSION's state takes, whatever its length: the
        entries without a token axis (0 for a layout of rows only)."""
        return self._entry_bytes(False)

    # -- block accounting ---------------------------------------------------
    def _incref(self, block):
        self.refcount[block] += 1

    def _release(self, block):
        self.refcount[block] -= 1
        if self.refcount[block] < 0:
            raise AssertionError(f"block {block} refcount underflow")
        if self.refcount[block] == 0 and block != 0:
            self._free_blocks.append(block)

    def alloc_blocks(self, n):
        """``n`` fresh private blocks (refcount 1 each), reclaiming
        trie-only blocks LRU-first if the free list runs dry; raises
        :class:`CacheOOM` when live sessions hold everything."""
        short = n - len(self._free_blocks)
        if short > 0 and self.trie is not None:
            # a span only where the trie has to give blocks back
            with telemetry.span(telemetry.DECODE_ALLOC_BLOCKS,
                                blocks=n) as sp:
                sp.add(reclaimed=self.trie.reclaim(
                    short, self.refcount, self._release))
        if n > len(self._free_blocks):
            raise CacheOOM(
                f"need {n} blocks, {len(self._free_blocks)} free "
                f"(pool {self.num_blocks}, in use {self.blocks_in_use})")
        out = [self._free_blocks.pop() for _ in range(n)]
        for b in out:
            self.refcount[b] += 1
        return out

    # -- slot lifecycle -----------------------------------------------------
    def alloc(self):
        """A free slot index, or None when all slots are occupied
        (blocks are allocated separately via :meth:`map_session`)."""
        return self._free.pop() if self._free else None

    def map_session(self, slot, shared_blocks, own_blocks, length):
        """Install a session's block-table row: ``shared_blocks``
        (trie-matched, this call takes the session's refs) then
        ``own_blocks`` (already ref'd by :meth:`alloc_blocks`), cursor
        to ``length``."""
        blocks = list(shared_blocks) + list(own_blocks)
        if len(blocks) > self.blocks_per_slot:
            raise ValueError(
                f"{len(blocks)} blocks > blocks_per_slot "
                f"{self.blocks_per_slot}")
        for b in shared_blocks:
            self._incref(b)
        row = self.block_tables[slot]
        row[:] = 0
        row[:len(blocks)] = blocks
        self._nblocks[slot] = len(blocks)
        self.lengths[slot] = int(length)

    def ensure_capacity(self, slot, upto):
        """Grow ``slot``'s table so logical positions < ``upto`` are
        backed by real blocks (decode writes past the prompt)."""
        upto = min(int(upto), self.blocks_per_slot * self.block_size)
        need = -(-upto // self.block_size)
        have = int(self._nblocks[slot])
        if need <= have:
            return
        fresh = self.alloc_blocks(need - have)
        self.block_tables[slot, have:need] = fresh
        self._nblocks[slot] = need

    def retire(self, slot):
        """Free the slot and drop the session's block refs — shared
        blocks survive while the trie (or another session) still
        references them."""
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        for b in self.block_tables[slot, :self._nblocks[slot]]:
            self._release(int(b))
        self.block_tables[slot] = 0
        self._nblocks[slot] = 0
        self.lengths[slot] = 0
        self._free.append(slot)

    # -- prefix sharing -----------------------------------------------------
    def match_prefix(self, prompt):
        """(shared physical blocks, matched token count) for the
        longest resident whole-block prefix of ``prompt`` — capped one
        token short of the full prompt so admission always has a real
        tail to prefill (the tail's last position produces the
        first-token logits)."""
        if self.trie is None:
            return [], 0
        limit = (len(prompt) - 1) // self.block_size
        blocks = self.trie.match(prompt, limit=limit)
        return blocks, len(blocks) * self.block_size

    def register_prompt(self, slot, prompt):
        """Offer the session's whole-block prompt prefix to the trie
        (post-prefill, so the mapped blocks' content is final)."""
        if self.trie is None:
            return
        nb = len(prompt) // self.block_size
        self.trie.insert(prompt, [int(b) for b in
                                  self.block_tables[slot, :nb]],
                         self._incref)

    # -- device writes ------------------------------------------------------
    def insert_tail(self, slot, *rows_start_length, row=None):
        """``insert_tail(slot, *rows, start, length)``: install a
        prefill's output, one ``[layers, ...]`` array per pool: rows per
        token into the slot's blocks covering positions ``[start, start +
        length)``, per-session state over row ``slot`` of every layer of
        its pool (what the slot's last session left there is gone).  With
        ``row=i`` the arrays are a whole prefill's ``[B, layers, ...]``
        outputs, still on the device, and row ``i`` of them is meant.
        ``start`` must be block-aligned (trie matches are
        whole-block); what the arrays hold past ``length`` (a bucket's
        padding) lands in the session-private remainder of the last
        block, which decode overwrites in order, and in the sentinel."""
        *rows, start, length = rows_start_length
        bs = self.block_size
        if start % bs:
            raise ValueError(f"tail start {start} not block-aligned ({bs})")
        t = int(length)
        if start + t > self.max_seq:
            raise ValueError(
                f"prefill end {start + t} > max_seq {self.max_seq}")
        if row is None:
            rows, row = [r[None] for r in rows], 0
        paged = next(i for i, ax in enumerate(self._token_axes)
                     if ax is not None)
        padded = rows[paged].shape[self._token_axes[paged] + 2]
        first = start // bs
        nch = -(-t // bs)
        blocks = np.zeros((-(-padded // bs),), np.int32)
        blocks[:nch] = self.block_tables[slot, first:first + nch]
        self.pools = _kv_insert(self._token_axes, bs)(
            self.pools, blocks, tuple(rows), np.int32(row), np.int32(slot))

    # -- introspection ------------------------------------------------------
    @property
    def occupancy(self):
        """Slots a session holds, and with each its row of every state
        entry."""
        return self.slots - len(self._free)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def blocks_in_use(self):
        """Blocks referenced by live sessions and/or the trie (the
        sentinel is excluded)."""
        return self.num_blocks - 1 - len(self._free_blocks)

    def leaked_blocks(self):
        """Refcount lint: block ids that are neither free, sentinel,
        session-referenced, nor trie-referenced — must always be
        empty.  Blocks only: per-session state has no count to drift, a
        slot's row goes back with the slot (``occupancy``)."""
        refs = np.zeros((self.num_blocks,), np.int64)
        refs[0] = 1
        for slot in range(self.slots):
            for b in self.block_tables[slot, :self._nblocks[slot]]:
                refs[int(b)] += 1
        if self.trie is not None:
            for node in self.trie.walk():
                refs[node.block] += 1
        if not np.array_equal(refs, self.refcount):
            bad = np.nonzero(refs != self.refcount)[0]
            raise AssertionError(
                f"refcount drift at blocks {bad.tolist()}: "
                f"counted {refs[bad].tolist()}, "
                f"stored {self.refcount[bad].tolist()}")
        free = set(self._free_blocks)
        return [b for b in range(1, self.num_blocks)
                if self.refcount[b] == 0 and b not in free]

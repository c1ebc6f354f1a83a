"""Paged (block) KV cache for LLM serving: host block table + device pools.

A dense per-sequence KV cache sizes every sequence at ``max_len`` —
HBM pays for the worst case while the mean sequence uses a fraction of
it, and two requests sharing a long system prompt pay for it twice.
The paged layout (vLLM's PagedAttention; the TPU serving comparison in
arXiv:2605.25645 attributes most of its throughput win to it) instead
carves the cache into fixed ``[num_blocks, block_len, heads, head_dim]``
pools and gives each sequence a CHAIN of block indices: memory is
allocated in ``block_len``-token quanta as decoding advances, and a
block holding a popular prompt prefix is SHARED copy-free between
sequences via refcounts.

Two halves, same split as continuous batching
(``sched.SlotScheduler`` / ``dl.ContinuousGenerator``):

- **Host half (this module's** :class:`PagedKVManager` **— pure Python,
  no JAX)**: the block table. Free-list allocation, per-sequence chains,
  refcounted prefix reuse keyed by a rolling prompt-prefix hash (one
  hash per full ``block_len`` chunk, chained so a block's key commits to
  everything before it), LRU eviction of retired-but-cached blocks, and
  a block budget derived from the live HBM headroom (``obs.memory``).
  Importable and testable with no device — the serving control plane
  runs it from handler threads (CI style smoke asserts no jax).
- **Device half (lazy jax imports)**: pool init from a decoder's
  ``cache_spec()``, what a block of it costs at rest, and the scatter
  of a window's new cache rows through the block table. Attention reads
  the pools in place (``dl.pallas_paged_attention``).

Block 0 is RESERVED as the trash block: padded batch rows and inactive
slots point their block-table entries at it, so fixed-shape device
programs can always write "somewhere" without corrupting a live
sequence (reads of it are masked by sequence length).

THREE KINDS of cache array, one manager. A decoder's ``cache_spec()``
lists, a layer, entries ``(trailing shape, dtype[, kind])``:

- ``"token"`` (the default): one entry a token, ``[num_blocks, block_len,
  *shape]`` — keys and values, a latent;
- ``("every", n)``: one entry every ``n`` tokens, ``[num_blocks, block_len
  // n, *shape]``, addressed through the same chains — compressed keys
  (an entry belongs to the block that holds the LAST token it was made
  from, so a shared block's entries do not depend on who shares it);
- ``"seq"``: one entry a SEQUENCE whatever its length, ``[1 + state_slots,
  *shape]``, addressed by a ROW the manager hands out with the chain
  (``SequenceHandle.state_row``; row 0 is the trash row) — the recurrent
  state of a linear-attention layer. A layer may state SEVERAL, of
  different shape and type (a float32 matrix state and the last inputs
  of a causal convolution): each is a pool of its own and a sequence
  holds the SAME row in every one, so a row's price
  (:func:`state_row_bytes`), a snapshot and a restore
  (:func:`copy_state_rows`) are of all of them together.

With ``"seq"`` arrays a cached prefix is reusable only up to a boundary at
which a SNAPSHOT of the state exists: a row of the same pools that holds
the state after exactly that prefix, keyed by the prefix's chunk hash.
``allocate`` answers with the reused length AND the row to restore from
(``restore_row``; the engine copies it into the sequence's row on the
device) and says at which boundary of this prompt a snapshot is worth
taking (``snapshot_at``: its last whole chunk, when the prompt brings new
chunks); ``take_snapshot`` hands the row when prefill gets there and
``publish`` indexes it with the blocks. Snapshots are pinned while a
sequence still has to restore from them, go with the block they end when
that block is evicted, and are the first rows recycled when live
sequences need rows (one never restored before one restored, then the
least recently restored): then the prefix is a MISS, never a wrong answer. Rows
and blocks draw on ONE budget, in blocks (a row costs ``state_row_blocks``).

Obs families (federated fleet-wide, recorded by the history plane):
``kv_blocks_used`` / ``kv_blocks_free`` / ``kv_blocks_cached`` gauges,
``kv_prefix_hits_total`` / ``kv_prefix_misses_total`` /
``kv_prefix_tokens_reused_total`` / ``kv_evictions_total`` counters;
``kv_state_slots_used`` / ``kv_state_snapshots`` / ``kv_state_bytes``
gauges, ``kv_state_restores_total`` /
``kv_state_snapshot_evictions_total`` counters.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

from ..obs import registry as _default_registry

__all__ = ["PagedKVManager", "SequenceHandle", "OutOfBlocks",
           "blocks_for_hbm_budget", "pool_block_bytes", "init_pools",
           "scatter_positions", "scatter_rows", "entry_kind",
           "state_row_bytes", "copy_state_rows", "TRASH_ROW"]

#: the reserved trash block — device programs route padded/inactive
#: writes here; the host half never hands it to a sequence
TRASH_BLOCK = 0
#: the reserved trash row of every per-sequence array
TRASH_ROW = 0


class OutOfBlocks(RuntimeError):
    """The pool cannot serve an allocation: every non-reserved block is
    referenced by a live sequence (nothing evictable). Callers queue the
    sequence and retry at a later step boundary — admission control,
    not a crash."""


@dataclass
class SequenceHandle:
    """One sequence's view of the pool: the block chain and how many
    token positions are filled. ``prompt_len`` rides along so executors
    can split prefill cost from decode cost without a side channel."""
    seq_id: object
    chain: list[int]
    length: int
    prompt_len: int
    reused_tokens: int = 0
    # hashes for the full prompt chunks this sequence must publish into
    # the prefix index once prefill has actually filled them
    pending_publish: list[tuple[str, int]] = field(default_factory=list)
    # per-sequence arrays (``"seq"`` cache entries): the sequence's row,
    # the snapshot row its state is to be copied from before its first
    # prefill chunk (None: it starts from nothing), the prompt boundary
    # at which a snapshot is worth taking and the row it was taken into
    state_row: int | None = None
    restore_row: int | None = None
    snapshot_at: int | None = None
    snapshot_hash: str | None = None
    snapshot_row: int | None = None

    def to_state(self) -> dict:
        """JSON-able handoff payload (the mesh ``__lease__`` envelope
        carries dicts): everything the decode side needs to adopt the
        sequence."""
        return {"seq_id": self.seq_id, "chain": list(self.chain),
                "length": int(self.length),
                "prompt_len": int(self.prompt_len),
                "reused_tokens": int(self.reused_tokens),
                "state_row": self.state_row}

    @classmethod
    def from_state(cls, state: dict) -> "SequenceHandle":
        return cls(seq_id=state["seq_id"],
                   chain=[int(b) for b in state["chain"]],
                   length=int(state["length"]),
                   prompt_len=int(state["prompt_len"]),
                   reused_tokens=int(state.get("reused_tokens", 0)),
                   state_row=state.get("state_row"))


def _chunk_hash(prev: str, tokens) -> str:
    """Rolling hash for one full ``block_len`` chunk: commits to the
    previous chunk's hash, so equal blocks match only on equal whole
    prefixes (prefix reuse must never splice a block into a different
    history)."""
    h = hashlib.blake2b(prev.encode(), digest_size=16)
    h.update(b"|")
    # the chunk's ids as eight bytes each: no id runs into its neighbour,
    # and a 65,536-token prompt is hashed in a millisecond, not in twenty
    # (first chip runs, PR 33: the host's time a request went with the
    # length of its document)
    h.update(np.ascontiguousarray(tokens, dtype=np.int64).tobytes())
    return h.hexdigest()


LANES = 128     # the minor dimension of a TPU tile


def _tiled_bytes(block_len: int, shape: tuple, dtype) -> int:
    """Bytes of one ``[block_len, *shape]`` block in the tiled layout
    the TPU kernel reads it in: the minor dim rounds up to 128 lanes,
    the one before it to the dtype's sublane count (8 rows of 32 bits; a
    dim below that to its power of two)."""
    itemsize = np.dtype(dtype).itemsize
    sublanes = 8 * max(4 // itemsize, 1)
    dims = [int(block_len), *(int(n) for n in shape)]
    dims[-1] = -(-dims[-1] // LANES) * LANES
    if dims[-2] < sublanes:
        dims[-2] = 1 << (dims[-2] - 1).bit_length()
    else:
        dims[-2] = -(-dims[-2] // sublanes) * sublanes
    return int(np.prod(dims)) * itemsize


def entry_kind(entry) -> tuple:
    """``(kind, n)`` of one ``cache_spec()`` entry ``(shape, dtype[,
    kind])``: ``("token", 1)``, ``("every", n)`` or ``("seq", 0)``."""
    kind = entry[2] if len(entry) > 2 else "token"
    if kind == "token":
        return "token", 1
    if kind == "seq":
        return "seq", 0
    if isinstance(kind, (tuple, list)) and len(kind) == 2 \
            and kind[0] == "every" and int(kind[1]) >= 1:
        return "every", int(kind[1])
    raise ValueError(f"cache_spec entry kind {kind!r} is not \"token\", "
                     "(\"every\", n) or \"seq\"")


def _block_rows(entry, block_len: int) -> int:
    """Rows ONE block of a chained entry holds; 0 for a ``"seq"`` entry."""
    kind, n = entry_kind(entry)
    if kind == "seq":
        return 0
    if block_len % n:
        raise ValueError(f"block_len {block_len} is not a multiple of the "
                         f"{n} tokens an (\"every\", {n}) entry covers")
    return block_len // n


def pool_block_bytes(spec, block_len: int) -> int:
    """Bytes ONE block is priced at when the pools of a decoder's
    ``cache_spec()`` (per layer, the arrays one token takes as
    ``(trailing shape, dtype)``, or one every ``n`` tokens) are sized from
    an HBM budget; ``"seq"`` entries are rows, not blocks
    (:func:`state_row_bytes`). Every
    array a step can hold is counted in the tiled layout the TPU kernel
    reads a ``[block_len, *shape]`` block in (:func:`_tiled_bytes`), so
    8 heads of 64 cost 2x their logical bytes and 2 heads of 16 cost 8x:
    every layer's arrays at rest, plus the widest layer's twice more
    (coming and going), which XLA materializes in that layout around
    the attention kernel where it keeps a pool with a narrow minor dim
    compact at rest and re-lays it out on every step (first chip run,
    PR 23: sized from logical bytes, 8M blocks of [4, 2, 16] asked for
    one 32.9 GB padded copy). Pure shape arithmetic — no JAX."""
    layers = [sum(_tiled_bytes(_block_rows(e, block_len), e[0], e[1])
                  for e in layer if _block_rows(e, block_len))
              for layer in spec]
    return sum(layers) + 2 * max(layers)


def state_row_bytes(spec) -> int:
    """Bytes ONE row of the ``"seq"`` entries of a ``cache_spec()`` takes,
    every such entry of every layer (0: the decoder keeps nothing a
    sequence)."""
    return sum(int(np.prod(e[0])) * np.dtype(e[1]).itemsize
               for layer in spec for e in layer
               if entry_kind(e)[0] == "seq")


def blocks_for_hbm_budget(block_bytes: int, *, fraction: float = 0.5,
                          default: int = 0) -> int:
    """How many KV blocks fit in ``fraction`` of the CURRENT free HBM
    (``obs.memory.device_memory_stats``; limit − in_use of the first
    local device). Returns ``default`` when no backend/allocator stats
    exist (CPU, host-only process) — the no-JAX half must size pools
    without a device."""
    from ..obs.memory import device_memory_stats
    stats = device_memory_stats()
    if not stats or block_bytes <= 0:
        return int(default)
    s = stats[0]
    limit = s.get("bytes_limit")
    in_use = s.get("bytes_in_use")
    if not limit:
        return int(default)
    free = max(int(limit) - int(in_use or 0), 0)
    return max(int(free * float(fraction)) // int(block_bytes), 0)


class PagedKVManager:
    """Host-side block table: pure-Python bookkeeping, no JAX.

    ``num_blocks`` counts the WHOLE pool including the reserved trash
    block 0; ``block_budget`` (optional, defaults to every allocatable
    block) caps how many blocks may be used+cached at once — set it
    from :func:`blocks_for_hbm_budget` to keep the KV pools under the
    live HBM headroom, or lower it at runtime via
    :meth:`set_block_budget` (cached blocks are LRU-evicted to fit).

    Lifecycle per sequence::

        h = mgr.allocate(seq_id, prompt_tokens)   # prefix reuse happens here
        mgr.publish(seq_id)                       # after prefill fills blocks
        mgr.ensure_capacity(seq_id, n)            # before writes past capacity
        mgr.advance(seq_id, k)                    # after k tokens committed
        mgr.release(seq_id)                       # blocks cached for reuse

    A released sequence's published prompt blocks stay in the prefix
    index (refcount 0, LRU-ordered) until eviction recycles them — the
    "cache" in KV cache hit rate.
    """

    def __init__(self, num_blocks: int, block_len: int, *,
                 block_budget: int | None = None, service: str = "llm",
                 registry=None, state_slots: int = 0,
                 state_row_bytes: int = 0, state_row_blocks: int = 0):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the "
                             "reserved trash block)")
        if block_len < 1:
            raise ValueError("block_len must be >= 1")
        reg = registry if registry is not None else _default_registry
        self.num_blocks = int(num_blocks)
        self.block_len = int(block_len)
        self.service = service
        self._free: deque[int] = deque(range(1, self.num_blocks))
        self._ref: dict[int, int] = {}
        self._seqs: dict[object, SequenceHandle] = {}
        # published full prompt chunks: hash -> block, block -> hash
        self._prefix_index: dict[str, int] = {}
        self._block_hash: dict[int, str] = {}
        # zero-ref published blocks, least-recently-retired first
        self._lru: OrderedDict[int, str] = OrderedDict()
        # rows of the per-sequence arrays: 1..state_slots (row 0 is the
        # trash row); a live sequence holds one, a snapshot holds one
        self.state_slots = int(state_slots)
        self.state_row_bytes = int(state_row_bytes)
        self.state_row_blocks = int(state_row_blocks)
        self._state_free: deque[int] = deque(range(1, self.state_slots + 1))
        self._state_live: set[int] = set()
        # snapshots: chunk hash -> row, least recently used first; a row's
        # pins are the sequences that still have to restore from it
        self._snapshots: OrderedDict[str, int] = OrderedDict()
        self._snapshot_pins: dict[int, int] = {}
        self._budget_cap = self.num_blocks - 1 \
            + self.state_slots * self.state_row_blocks
        self._budget = int(block_budget) if block_budget else \
            self._budget_cap
        self._budget = max(min(self._budget, self._budget_cap), 1)
        self._g_used = reg.gauge(
            "kv_blocks_used",
            "KV blocks referenced by live sequences, by service")
        self._g_free = reg.gauge(
            "kv_blocks_free",
            "KV blocks on the free list (never-written or recycled), "
            "by service")
        self._g_cached = reg.gauge(
            "kv_blocks_cached",
            "retired zero-ref KV blocks still indexed for prefix "
            "reuse, by service")
        self._c_hits = reg.counter(
            "kv_prefix_hits_total",
            "prompt-prefix blocks served copy-free from the index, "
            "by service")
        self._c_misses = reg.counter(
            "kv_prefix_misses_total",
            "full prompt chunks that found no indexed block, by service")
        self._c_reused = reg.counter(
            "kv_prefix_tokens_reused_total",
            "prompt tokens whose prefill was skipped via prefix reuse, "
            "by service")
        self._c_evict = reg.counter(
            "kv_evictions_total",
            "cached KV blocks recycled under pool/HBM pressure, "
            "by service")
        self._g_state_used = reg.gauge(
            "kv_state_slots_used",
            "rows of the per-sequence cache arrays held by live "
            "sequences, by service")
        self._g_state_snap = reg.gauge(
            "kv_state_snapshots",
            "rows of the per-sequence cache arrays held by state "
            "snapshots, by service")
        self._g_state_bytes = reg.gauge(
            "kv_state_bytes",
            "bytes of per-sequence cache held by live sequences and "
            "snapshots, by service")
        self._c_restores = reg.counter(
            "kv_state_restores_total",
            "sequences whose state was restored from a snapshot, "
            "by service")
        self._c_snap_evict = reg.counter(
            "kv_state_snapshot_evictions_total",
            "state snapshots dropped, with the block they ended or for "
            "a live sequence's row, by service")
        self._publish_gauges()

    # -- internals ---------------------------------------------------------
    def _publish_gauges(self) -> None:
        self._g_used.set(len(self._ref), service=self.service)
        self._g_free.set(len(self._free), service=self.service)
        self._g_cached.set(len(self._lru), service=self.service)
        if self.state_slots:
            live, snaps = len(self._state_live), len(self._snapshots)
            self._g_state_used.set(live, service=self.service)
            self._g_state_snap.set(snaps, service=self.service)
            self._g_state_bytes.set((live + snaps) * self.state_row_bytes,
                                    service=self.service)

    def _held(self) -> int:
        """What is drawn on the budget, in blocks: used and cached
        blocks, and the rows of live sequences and snapshots."""
        return len(self._ref) + len(self._lru) + self.state_row_blocks * (
            len(self._state_live) + len(self._snapshots))

    def _in_budget(self) -> bool:
        return self._held() < self._budget

    def _drop_snapshot(self, h: str) -> None:
        row = self._snapshots.pop(h)
        self._snapshot_pins.pop(row, None)
        self._state_free.append(row)
        self._c_snap_evict.inc(1, service=self.service)

    def _take_state_row(self) -> int:
        """A free row of the per-sequence arrays; the least recently used
        snapshot no sequence waits on gives its row up first."""
        if not self._state_free:
            victim = next((h for h, row in self._snapshots.items()
                           if not self._snapshot_pins.get(row)), None)
            if victim is None:
                raise OutOfBlocks(
                    f"all {self.state_slots} state rows held by live "
                    "sequences and pinned snapshots")
            self._drop_snapshot(victim)
        return self._state_free.popleft()

    def _evict_one(self) -> int | None:
        """Recycle the least-recently-retired cached block onto the
        free list; None when nothing is evictable."""
        if not self._lru:
            return None
        block, h = self._lru.popitem(last=False)
        self._prefix_index.pop(h, None)
        self._block_hash.pop(block, None)
        if h in self._snapshots:        # the snapshot goes with its block
            self._drop_snapshot(h)
        self._free.append(block)
        self._c_evict.inc(1, service=self.service)
        return block

    def _take_block(self) -> int:
        # budget first: even with free blocks in hand, used+cached must
        # stay under the HBM-derived cap, so pressure evicts the cache
        # before it grows the working set
        while not self._in_budget():
            if self._evict_one() is None:
                raise OutOfBlocks(
                    f"block budget {self._budget} exhausted by live "
                    f"sequences ({len(self._ref)} blocks referenced)")
        if not self._free and self._evict_one() is None:
            raise OutOfBlocks(
                f"all {self.num_blocks - 1} blocks referenced by live "
                "sequences — queue the request and retry at the next "
                "step boundary")
        return self._free.popleft()

    # -- intake ------------------------------------------------------------
    def allocate(self, seq_id, prompt_tokens) -> SequenceHandle:
        """Build ``seq_id``'s chain for ``prompt_tokens``: reuse indexed
        blocks for the longest matching whole-chunk prefix (refcount++,
        copy-free), allocate fresh blocks for the rest. The handle's
        ``reused_tokens`` tells the prefill executor where to start —
        the TTFT win is exactly the prefill it skips.

        With per-sequence arrays (``state_slots``) the sequence also
        takes a row, and the reused prefix ends at the longest matching
        boundary that has a state SNAPSHOT and lies before the prompt's
        last token (the state cannot be rewound to re-feed it):
        ``restore_row`` names the snapshot's row, pinned until
        :meth:`restored`; with no such boundary the prefix is a miss."""
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        prompt = np.asarray(prompt_tokens, dtype=np.int64).reshape(-1)
        if not len(prompt):
            raise ValueError("empty prompt")
        bl = self.block_len
        full_chunks = len(prompt) // bl
        hashes: list[str] = []
        h = ""
        for c in range(full_chunks):
            h = _chunk_hash(h, prompt[c * bl:(c + 1) * bl])
            hashes.append(h)
        matched = 0
        while matched < full_chunks and hashes[matched] in self._prefix_index:
            matched += 1
        restore_row = None
        if self.state_slots:
            matched = min(matched, (len(prompt) - 1) // bl)
            while matched and hashes[matched - 1] not in self._snapshots:
                matched -= 1
            if matched:
                restore_row = self._snapshots[hashes[matched - 1]]
        chain: list[int] = []
        pending: list[tuple[str, int]] = []
        reused = matched * bl
        state_row = None
        pinned = False
        try:
            for c, h in enumerate(hashes):
                if c < matched:
                    block = self._prefix_index[h]
                    self._c_hits.inc(1, service=self.service)
                    self._ref[block] = self._ref.get(block, 0) + 1
                    if block in self._lru:       # revived from cache
                        del self._lru[block]
                    chain.append(block)
                    continue
                self._c_misses.inc(1, service=self.service)
                block = self._take_block()
                self._ref[block] = 1
                chain.append(block)
                pending.append((h, block))
            # tail block for the partial prompt chunk; decode growth is
            # on-demand via ensure_capacity
            if len(prompt) % bl:
                block = self._take_block()
                self._ref[block] = 1
                chain.append(block)
            if self.state_slots:
                if restore_row is not None:      # not to be recycled below
                    self._snapshot_pins[restore_row] = \
                        self._snapshot_pins.get(restore_row, 0) + 1
                    self._snapshots.move_to_end(hashes[matched - 1])
                    pinned = True
                state_row = self._take_state_row()
                self._state_live.add(state_row)
        except OutOfBlocks:
            # unwind: a half-allocated chain must not leak references
            for b in chain:
                self._unref(b)
            if pinned:
                self._unpin(restore_row)
            self._publish_gauges()
            raise
        if reused:
            self._c_reused.inc(reused, service=self.service)
        handle = SequenceHandle(seq_id=seq_id, chain=chain,
                                length=reused, prompt_len=len(prompt),
                                reused_tokens=reused,
                                pending_publish=pending,
                                state_row=state_row,
                                restore_row=restore_row)
        if self.state_slots and pending:
            # the state after the prompt's last whole chunk is worth
            # keeping: the boundary the new blocks make reusable
            handle.snapshot_at = full_chunks * bl
            handle.snapshot_hash = hashes[-1]
        self._seqs[seq_id] = handle
        self._publish_gauges()
        return handle

    # -- per-sequence rows and their snapshots -----------------------------
    def _unpin(self, row: int) -> None:
        pins = self._snapshot_pins.get(row, 0) - 1
        if pins > 0:
            self._snapshot_pins[row] = pins
        else:
            self._snapshot_pins.pop(row, None)

    def restored(self, seq_id) -> None:
        """The engine has copied ``restore_row`` into the sequence's row
        (or is about to, in program order): the snapshot is free to go."""
        handle = self._seqs[seq_id]
        if handle.restore_row is not None:
            self._unpin(handle.restore_row)
            handle.restore_row = None
            self._c_restores.inc(1, service=self.service)

    def take_snapshot(self, seq_id) -> int | None:
        """A row for the snapshot of ``seq_id``'s state at its
        ``snapshot_at`` boundary, asked for when prefill has got there;
        None when there is nothing to take or no row to spare (then the
        new blocks are published without one and reuse stops short of
        them). :meth:`publish` indexes it."""
        handle = self._seqs[seq_id]
        if handle.snapshot_at is None or handle.snapshot_row is not None \
                or handle.snapshot_hash in self._snapshots:
            return None
        try:
            if not self._in_budget() and self._evict_one() is None:
                return None
            handle.snapshot_row = self._take_state_row()
        except OutOfBlocks:
            return None
        return handle.snapshot_row

    def state_rows(self, seq_ids) -> np.ndarray:
        """``[len(seq_ids)]`` int32 rows of the per-sequence arrays;
        ``None`` entries (empty slots) and sequences without a row get
        the trash row."""
        out = np.full(len(seq_ids), TRASH_ROW, np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is not None and self._seqs[sid].state_row is not None:
                out[i] = self._seqs[sid].state_row
        return out

    def publish(self, seq_id) -> int:
        """Index ``seq_id``'s freshly prefilled full prompt chunks for
        future prefix reuse. Call AFTER the prefill executor has written
        the blocks — publishing earlier would let a concurrent allocate
        share a block whose kv is still zeros. Returns chunks published."""
        handle = self._seqs[seq_id]
        n = 0
        for h, block in handle.pending_publish:
            # first writer wins: two identical prompts racing through
            # prefill both hold private blocks; only one gets indexed
            if h not in self._prefix_index and block in self._ref:
                self._prefix_index[h] = block
                self._block_hash[block] = h
                n += 1
        handle.pending_publish = []
        if handle.snapshot_row is not None:
            # the snapshot is reusable with the blocks up to it; where
            # those did not get indexed, or a twin got there first, the
            # row goes back
            h = handle.snapshot_hash
            if h in self._prefix_index and h not in self._snapshots:
                # a snapshot earns its place by being restored: a new
                # one is the first to go, so that prompts whose own
                # chunks nobody asks for again cannot push out the
                # snapshots that are restored all the time
                self._snapshots[h] = handle.snapshot_row
                self._snapshots.move_to_end(h, last=False)
            else:
                self._state_free.append(handle.snapshot_row)
            handle.snapshot_row = None
            handle.snapshot_at = None
            self._publish_gauges()
        return n

    # -- growth / accounting -----------------------------------------------
    def capacity(self, seq_id) -> int:
        return len(self._seqs[seq_id].chain) * self.block_len

    def length(self, seq_id) -> int:
        return self._seqs[seq_id].length

    def handle(self, seq_id) -> SequenceHandle:
        return self._seqs[seq_id]

    def ensure_capacity(self, seq_id, tokens: int) -> SequenceHandle:
        """Grow ``seq_id``'s chain until it can hold ``tokens`` positions
        (speculative decode writes up to k+1 ahead each step)."""
        handle = self._seqs[seq_id]
        while len(handle.chain) * self.block_len < tokens:
            block = self._take_block()
            self._ref[block] = 1
            handle.chain.append(block)
        self._publish_gauges()
        return handle

    def advance(self, seq_id, n: int = 1) -> int:
        """Account ``n`` committed token positions; returns the new
        length. Positions must already be within capacity."""
        handle = self._seqs[seq_id]
        new_len = handle.length + int(n)
        if new_len > len(handle.chain) * self.block_len:
            raise ValueError(
                f"sequence {seq_id!r} advanced past capacity "
                f"({new_len} > {len(handle.chain)} blocks × "
                f"{self.block_len})")
        handle.length = new_len
        return handle.length

    # -- retirement --------------------------------------------------------
    def _unref(self, block: int) -> None:
        refs = self._ref.get(block, 0) - 1
        if refs > 0:
            self._ref[block] = refs
            return
        self._ref.pop(block, None)
        h = self._block_hash.get(block)
        if h is not None and self._prefix_index.get(h) == block:
            self._lru[block] = h        # retire into the reuse cache
            self._lru.move_to_end(block)
        else:
            self._block_hash.pop(block, None)
            self._free.append(block)

    def release(self, seq_id) -> None:
        """Drop the sequence: published blocks retire into the LRU reuse
        cache, everything else returns to the free list."""
        handle = self._seqs.pop(seq_id)
        for block in handle.chain:
            self._unref(block)
        if handle.restore_row is not None:
            self._unpin(handle.restore_row)
        for row in (handle.state_row, handle.snapshot_row):
            if row is not None:
                self._state_live.discard(row)
                self._state_free.append(row)
        self._publish_gauges()

    # -- handoff (prefill -> decode over the mesh lease plumbing) ----------
    def export_seq(self, seq_id) -> dict:
        """Detach the sequence for handoff: ownership of its block
        references moves WITH the returned payload (the manager keeps
        the refcounts; the seq is simply no longer addressable here
        until :meth:`adopt` re-registers it). Round-trips through JSON
        — the shape the mesh ``__lease__`` envelope carries."""
        handle = self._seqs.pop(seq_id)
        if handle.pending_publish:
            raise ValueError(
                f"sequence {seq_id!r} still has unpublished prefill "
                "blocks — publish() before handoff")
        self._publish_gauges()
        return handle.to_state()

    def adopt(self, state: dict) -> SequenceHandle:
        """Re-register an exported sequence (same pool — prefill and
        decode executors share the device pools on a host; cross-host
        adoption additionally ships the block contents)."""
        handle = SequenceHandle.from_state(state)
        if handle.seq_id in self._seqs:
            raise ValueError(f"sequence {handle.seq_id!r} already "
                             "registered")
        for block in handle.chain:
            if block not in self._ref:
                raise ValueError(
                    f"handoff chain references unowned block {block} — "
                    "the payload does not match this pool")
        if handle.state_row is not None \
                and handle.state_row not in self._state_live:
            raise ValueError(
                f"handoff names state row {handle.state_row}, which no "
                "exported sequence of this pool holds")
        self._seqs[handle.seq_id] = handle
        self._publish_gauges()
        return handle

    # -- device bridge -----------------------------------------------------
    def block_rows(self, seq_ids, max_blocks: int) -> np.ndarray:
        """``[len(seq_ids), max_blocks]`` int32 block table for the
        fixed-shape device step: each row is the sequence's chain padded
        with the trash block. ``None`` entries (empty slots) become
        all-trash rows."""
        rows = np.full((len(seq_ids), int(max_blocks)), TRASH_BLOCK,
                       np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is None:
                continue
            chain = self._seqs[sid].chain
            if len(chain) > max_blocks:
                raise ValueError(
                    f"sequence {sid!r} has {len(chain)} blocks > "
                    f"max_blocks={max_blocks}")
            rows[i, :len(chain)] = chain
        return rows

    # -- budget / introspection --------------------------------------------
    def set_block_budget(self, budget: int) -> int:
        """Lower (or raise) the used+cached cap; cached blocks are
        LRU-evicted immediately to fit. Returns blocks evicted — the
        fleet health plane calls this when ``mem_hbm_*`` pressure
        crosses its watermark.

        Eviction here aligns with :meth:`_take_block`'s strict
        ``used + cached < budget`` pre-allocation invariant: a shrink
        pays its whole eviction debt now (counted
        ``kv_evictions_total``), so the next ``allocate`` never evicts
        on the lowered budget's behalf. Stopping at ``== budget`` — the
        old behaviour — left exactly one cached block to be reclaimed
        lazily at the next allocation."""
        self._budget = max(min(int(budget), self._budget_cap), 1)
        evicted = 0
        while self._held() >= self._budget:
            if self._evict_one() is None:
                break
            evicted += 1
        self._publish_gauges()
        return evicted

    @property
    def block_budget(self) -> int:
        return self._budget

    def stats(self) -> dict:
        """One-glance pool state (the bench banks hit rate from the
        registry; this is the debugging view)."""
        return {
            "blocks": self.num_blocks,
            "block_len": self.block_len,
            "budget": self._budget,
            "used": len(self._ref),
            "free": len(self._free),
            "cached": len(self._lru),
            "sequences": len(self._seqs),
            "indexed_prefixes": len(self._prefix_index),
            "state_rows_live": len(self._state_live),
            "state_snapshots": len(self._snapshots),
        }


# --------------------------------------------------------------- device half
# Everything below imports jax lazily: the bookkeeping half above must
# stay importable (and CI-smoked) with no backend in the process.

def init_pools(spec, num_blocks: int, block_len: int, state_slots: int = 0):
    """Device pools for a decoder's ``cache_spec()``: per layer a tuple
    of arrays of zeros, one for each entry — ``[num_blocks, block_len,
    *trailing]`` for one a token (k and v of ``[heads * head_dim]`` for
    a ``TextEncoder``; one latent for latent attention), ``[num_blocks,
    block_len // n, *trailing]`` for one every ``n`` tokens, ``[1 +
    state_slots, *trailing]`` for one a sequence. Pools are
    allocated exactly as the decoder states them: a decoder that wants
    its minor axis padded to whole lanes says so in its spec."""
    import jax.numpy as jnp

    def pool(entry):
        shape = tuple(int(n) for n in entry[0])
        rows = _block_rows(entry, int(block_len))
        lead = (int(num_blocks), rows) if rows else (1 + int(state_slots),)
        return jnp.zeros(lead + shape, entry[1])

    return tuple(tuple(pool(entry) for entry in layer) for layer in spec)


def copy_state_rows(spec, pools, src, dst):
    """Copy rows ``src`` onto rows ``dst`` ([n] int32 each) of every
    per-sequence array of ``pools`` — a snapshot taken, a snapshot
    restored — on the device; everything else is handed back as it
    came. A pair ``(TRASH_ROW, TRASH_ROW)`` pads the call."""
    return tuple(
        tuple(pool.at[dst].set(pool[src])
              if entry_kind(entry)[0] == "seq" else pool
              for entry, pool in zip(layer_spec, layer_pools))
        for layer_spec, layer_pools in zip(spec, pools))


def _flat_positions(rows, pos, block_len: int):
    """[S, w] absolute positions -> flat pool indices via the block
    table: ``rows[s, p // bl] * bl + p % bl``. Out-of-chain positions
    clamp into the trash block's row (rows pads with TRASH_BLOCK)."""
    import jax.numpy as jnp
    bi = jnp.clip(pos // block_len, 0, rows.shape[1] - 1)   # [S, w]
    block = jnp.take_along_axis(rows, bi, axis=1)           # [S, w]
    return block * block_len + pos % block_len


def scatter_positions(pools, rows, pos, new_kv, valid=None):
    """Write per-layer ``[S, w, *trailing]`` arrays (k and v of ``[H,
    hd]``, or one latent) into the pools at absolute positions ``pos``
    ([S, w]) through the block table. Positions with ``valid`` ([S, w]
    bool) false — padded prefill rows, inactive decode slots — are
    redirected into the trash block's first row, so every program
    instance writes a fixed index set (shape-stable) without ever
    touching a live chain. Live chains are disjoint, so the scatter has
    no real-block collisions and stays deterministic."""
    import jax.numpy as jnp
    out = []
    for layer_pools, layer_new in zip(pools, new_kv):
        NB, BL = layer_pools[0].shape[:2]
        fidx = _flat_positions(rows, pos, BL)           # [S, w]
        if valid is not None:
            fidx = jnp.where(valid, fidx, TRASH_BLOCK * BL)
        out.append(tuple(
            pool.reshape(NB * BL, *pool.shape[2:]).at[fidx].set(new)
            .reshape(pool.shape)
            for pool, new in zip(layer_pools, layer_new)))
    return tuple(out)


def scatter_rows(pool, rows, index, new, valid):
    """Write ``new`` [S, n, *trailing] into ONE pool of an ``("every",
    n)`` entry, ``[num_blocks, rows_per_block, *trailing]``, at chain-wide
    row indices ``index`` [S, n] (row ``i`` of a chain is row ``i %
    rows_per_block`` of its ``i // rows_per_block``-th block); ``valid``
    [S, n] false sends a write to the trash block's first row."""
    import jax.numpy as jnp
    NB, rpb = pool.shape[:2]
    fidx = _flat_positions(rows, index, rpb)
    fidx = jnp.where(valid, fidx, TRASH_BLOCK * rpb)
    return pool.reshape(NB * rpb, *pool.shape[2:]).at[fidx].set(new) \
        .reshape(pool.shape)

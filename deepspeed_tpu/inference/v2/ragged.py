"""Ragged batching + paged KV cache management.

Capability analogue of the reference's inference-v2 ragged stack
(``inference/v2/ragged/`` — ``DSStateManager`` ragged_manager.py:19,
``RaggedBatchWrapper`` ragged_wrapper.py:31, ``BlockedKVCache``
kv_cache.py:40, ``BlockedAllocator`` blocked_allocator.py:11): sequences own
chains of fixed-size KV blocks from a shared pool, so memory scales with
tokens actually generated, and prefill/decode tokens from many requests batch
into one ragged forward.

TPU adaptation: XLA needs static shapes, so the "ragged" batch is a fixed
(max_tokens,) token buffer + per-sequence block tables padded to
``max_blocks_per_seq`` — the paged-attention kernel indexes KV through the
block table.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np


class BlockedAllocator:
    """Reference-counted free-list allocator over a fixed pool of KV blocks
    (reference: ``blocked_allocator.py:11``).

    ``allocate`` hands out blocks with refcount 1; ``free`` decrements and
    returns a block to the pool only when its last owner releases it —
    the substrate for cross-request block sharing (prefix cache: one KV
    block in many block tables).  A ``free`` of a block whose refcount is
    already 0 raises instead of silently corrupting the pool (the old
    free list extended unconditionally, so a double-free made the same
    block allocatable twice)."""

    def __init__(self, num_blocks: int):
        if num_blocks <= 0:
            raise ValueError("num_blocks must be positive")
        self._free: List[int] = list(range(num_blocks))
        self._refs: List[int] = [0] * num_blocks
        self.num_blocks = num_blocks
        #: blocks whose bytes were demoted off-device by the paging tier
        #: (``inference/v2/paging.py``) — they hold no pool id, but they
        #: are part of the resident KV footprint, so the consistency check
        #: extends to ``free + evictable + pinned + demoted == total +
        #: demoted`` (see ``PrefixCache.check_consistency``)
        self.demoted = 0

    def note_demote(self) -> None:
        """A device block's bytes moved to the host/spill tier (the block
        id itself was freed separately)."""
        self.demoted += 1

    def note_promote(self) -> None:
        """A demoted block's bytes came back on-device (or were dropped)."""
        if self.demoted <= 0:
            raise AssertionError("promote with no demoted blocks tracked")
        self.demoted -= 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"KV cache exhausted: requested {n} blocks, {len(self._free)} free")
        out = self._free[:n]
        del self._free[:n]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, block: int) -> None:
        """Add an owner to a live (allocated) block — shared-prefix use."""
        if not (0 <= block < self.num_blocks):
            raise ValueError(f"invalid block id {block}")
        if self._refs[block] <= 0:
            raise ValueError(f"incref on free block {block}")
        self._refs[block] += 1

    def refcount(self, block: int) -> int:
        if not (0 <= block < self.num_blocks):
            raise ValueError(f"invalid block id {block}")
        return self._refs[block]

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"invalid block id {b}")
        for b in blocks:
            if self._refs[b] <= 0:
                raise ValueError(
                    f"double-free of block {b} (refcount already 0)")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)

    def check_consistency(self) -> None:
        """Pool invariants: no duplicate free entries, every free block has
        refcount 0, and free + referenced partitions the pool exactly."""
        if len(self._free) != len(set(self._free)):
            raise AssertionError("duplicate block ids in the free list")
        for b in self._free:
            if self._refs[b] != 0:
                raise AssertionError(
                    f"free block {b} has refcount {self._refs[b]}")
        live = sum(1 for r in self._refs if r > 0)
        if live + len(self._free) != self.num_blocks:
            raise AssertionError(
                f"pool accounting broken: {live} live + "
                f"{len(self._free)} free != {self.num_blocks} total")
        if self.demoted < 0:
            raise AssertionError(f"negative demoted count {self.demoted}")


class StateSlots:
    """The slots of per-sequence state (a model with Mamba-2 layers keeps an
    SSM state and the conv's last inputs a sequence, not a block): a free
    list beside the block allocator, inside the one ``KVCacheManager``.  A
    sequence takes a slot when its first chunk is scheduled and gives it back
    when it retires, is cancelled, times out or is aborted.  What a slot held
    is never cleared: a step is told which rows start a sequence, and those
    start from zeros whatever the slot holds.  A slot is also the sequence's
    row in ``DecodeStateTable``, so a decode step's rows lie in slot order."""

    def __init__(self, num_slots: int):
        if num_slots <= 0:
            raise ValueError("num_slots must be positive")
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._owner: Dict[int, int] = {}  # slot -> uid

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def take(self, uid: int) -> int:
        if not self._free:
            raise MemoryError("state slots exhausted")
        slot = self._free.pop()
        self._owner[slot] = uid
        return slot

    def give(self, slot: int) -> None:
        if slot not in self._owner:
            raise ValueError(f"state slot {slot} given back twice")
        del self._owner[slot]
        self._free.append(slot)

    def check_consistency(self) -> None:
        if len(self._free) != len(set(self._free)):
            raise AssertionError("duplicate slots in the free list")
        if set(self._free) & set(self._owner):
            raise AssertionError("a state slot is both free and owned")
        if len(self._free) + len(self._owner) != self.num_slots:
            raise AssertionError(
                f"slot accounting broken: {len(self._owner)} owned + "
                f"{len(self._free)} free != {self.num_slots} total")


@dataclasses.dataclass
class SequenceDescriptor:
    """Reference: ``sequence_descriptor.py`` — one tracked request."""

    uid: int
    tokens: List[int]
    blocks: List[int] = dataclasses.field(default_factory=list)
    seen_tokens: int = 0  # tokens already in KV cache
    max_new_tokens: int = 128
    generated: int = 0
    done: bool = False
    in_decode: bool = False  # finished prefill (steady-state fast path)
    #: per-request sampling temperature; None inherits the step-level
    #: scalar (the pre-disaggregation deployment-wide knob)
    temperature: Optional[float] = None
    #: per-request sampling seed — rows with the same seed in one batch
    #: still draw independently (the row index is folded in on device)
    seed: int = 0
    #: device adapter-stack slot this request's rows read their LoRA
    #: factors from (serving/adapters.py assigns slots; 0 is the reserved
    #: null slot whose factors are all-zero, so base-only requests add an
    #: exact-zero delta and stay bit-identical to an adapterless engine)
    adapter_slot: int = 0
    #: ``blocks`` is indexed by logical block: ``blocks[j]`` holds positions
    #: ``[j * block_size, (j + 1) * block_size)``.  A windowed pool frees
    #: from the front: entries before ``first_block`` are stale ids that
    #: nothing reads, and ``reserved_blocks`` is what admission set aside
    #: for the chain (a windowed pool allocates as the sequence advances).
    first_block: int = 0
    reserved_blocks: int = 0
    #: the same three for the second pool: the window layers' of a model
    #: with two kinds of attention layer (its global layers' chain is
    #: ``blocks``), the window's of an EVA model (``blocks``: its summaries')
    win_blocks: List[int] = dataclasses.field(default_factory=list)
    win_first_block: int = 0
    win_reserved_blocks: int = 0
    #: where the sequence's per-sequence state lives (a model with state
    #: layers; ``StateSlots``), -1 while it holds none
    state_slot: int = -1

    @property
    def cur_len(self) -> int:
        return len(self.tokens)


def window_bound(window: int, max_chunk: int, block_size: int,
                 max_blocks_per_seq: int) -> int:
    """The most blocks of a windowed pool one sequence holds at a time: a
    step of ``max_chunk`` tokens whose oldest query sits at ``s`` touches
    positions ``s - window + 1 .. s + max_chunk - 1``, which lie in at most
    that many blocks wherever ``s`` falls in its block."""
    return min(max_blocks_per_seq,
               (window + max(max_chunk, 1) - 2) // block_size + 2)


class KVCacheManager:
    """Paged KV cache bookkeeping (host side) for ONE pool.

    The device-side cache is a (layers, num_blocks, block_size, kv_heads,
    head_dim) array; this manager owns the allocator and per-sequence block
    tables (reference ``BlockedKVCache``).

    ``window`` > 0 makes it the pool of sliding-window layers: a query at
    position ``p`` reads keys ``p - window < j <= p`` only, so a sequence
    holds at most ``bound`` blocks however long it grows (the window, one
    step's chunk of up to ``max_chunk`` tokens, and the partial blocks at
    both ends).  Admission then *reserves* that many (``reserve``), a step
    allocates what its chunk writes (``ensure_capacity``) and ``trim`` frees
    what fell behind the window; the table stays indexed by logical block,
    its freed entries stale and never read.  ``chain`` names the
    ``SequenceDescriptor`` fields the pool's chain lives in (``"win_"`` for
    the second pool of a model with two).

    ``tumbling`` beside ``window`` (EVA attention, ``models/eva.py``): the
    window does not slide a token at a time but is freed WHOLE, by the
    ``trim`` after the step that wrote its last token (``next_pos`` a
    multiple of ``window``); a row's chunk ends at the window's edge
    (``chunk_cap``), so ``bound`` is the window's own blocks and no more.

    ``per_window`` > 0 beside ``window``: the pool holds no tokens but that
    many ENTRIES for every window a sequence has completed (the window's
    summaries), so a sequence of ``n`` tokens has ``n // window *
    per_window`` entries in it.  It reserves and allocates as a windowed
    pool does, at the step that completes a window, and frees nothing while
    the sequence runs.

    ``state_slots`` > 0 (a model with state layers) gives the manager the
    sequences' state slots too (``StateSlots``): ``take_slot`` at admission,
    ``release`` gives slot and blocks back together, and
    ``check_consistency`` holds both allocators to their invariants."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, window: int = 0, max_chunk: int = 0,
                 chain: str = "", state_slots: int = 0,
                 tumbling: bool = False, per_window: int = 0):
        self.allocator = BlockedAllocator(num_blocks)
        self.slots = StateSlots(state_slots) if state_slots else None
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.window = window
        self.tumbling = tumbling
        self.per_window = per_window
        if per_window or not window:
            self.bound = max_blocks_per_seq
        elif tumbling:
            self.bound = min(max_blocks_per_seq, window // block_size)
        else:
            self.bound = window_bound(window, max_chunk, block_size,
                                      max_blocks_per_seq)
        self.reserved = 0  # windowed: blocks set aside for running sequences
        self.trimmed = 0  # windowed: blocks freed behind the window so far
        self._blocks, self._first, self._reserved = (
            chain + "blocks", chain + "first_block", chain + "reserved_blocks")
        # attached by the engine when the prefix cache is enabled; lets
        # capacity checks reclaim unreferenced cached blocks under pressure
        self.prefix_cache = None

    def chain(self, seq: SequenceDescriptor) -> List[int]:
        return getattr(seq, self._blocks)

    def blocks_for(self, tokens: int) -> int:
        """Blocks that hold what a sequence of ``tokens`` has put in the
        pool: its tokens, or ``per_window`` entries a completed window."""
        if self.per_window:
            tokens = tokens // self.window * self.per_window
        return -(-tokens // self.block_size)

    def reservation(self, total_tokens: int) -> int:
        """Blocks a sequence of ``total_tokens`` is admitted against."""
        return min(self.blocks_for(total_tokens), self.bound)

    def chunk_cap(self, start: int) -> int:
        """The most tokens a step's chunk that begins at ``start`` may hold:
        a tumbling window's rest (its queries then see one window)."""
        return self.window - start % self.window if self.tumbling else 1 << 30

    def opens_at(self, ctx: "np.ndarray") -> "np.ndarray":
        """Of decode rows with ``ctx`` tokens in the cache: those whose next
        token needs a block the chain may not have yet."""
        if self.per_window:  # the token that completes a window
            return (ctx + 1) % self.window == 0
        return ctx % self.block_size == 0

    def trims_at(self, ctx: "np.ndarray") -> "np.ndarray":
        """Of decode rows that now hold ``ctx`` tokens: those ``trim`` has a
        block to free for."""
        if self.tumbling:
            return ctx % self.window == 0
        oldest = ctx - self.window + 1  # the key the next query still reads
        return (oldest > 0) & (oldest % self.block_size == 0)

    @property
    def unreserved_blocks(self) -> int:
        """What admission may still promise: free blocks, less (windowed)
        what running sequences were promised and have not yet taken."""
        if not self.window:
            return self.allocator.free_blocks
        return self.allocator.num_blocks - self.reserved

    def blocks_needed(self, seq: SequenceDescriptor, new_tokens: int) -> int:
        need = self.blocks_for(seq.seen_tokens + new_tokens)
        return max(0, need - len(self.chain(seq)))

    def ensure_capacity(self, seq: SequenceDescriptor, new_tokens: int) -> bool:
        need = self.blocks_needed(seq, new_tokens)
        chain = self.chain(seq)
        if len(chain) + need > self.max_blocks_per_seq:
            return False
        short = need - self.allocator.free_blocks
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
        if need > self.allocator.free_blocks:
            return False
        if need:
            chain.extend(self.allocator.allocate(need))
        return True

    def reserve(self, seq: SequenceDescriptor, total_tokens: int,
                chunk: int) -> bool:
        """Admission: set the sequence's whole budget aside.  A full pool
        allocates it now; a windowed one counts ``reservation`` blocks and
        allocates this step's ``chunk``."""
        if not self.window:
            return self.ensure_capacity(seq, total_tokens)
        total = seq.seen_tokens + total_tokens
        need = self.reservation(total)
        if (need > self.unreserved_blocks
                or self.blocks_for(total) > self.max_blocks_per_seq):
            return False
        if not self.ensure_capacity(seq, chunk):
            return False
        setattr(seq, self._reserved, need)
        self.reserved += need
        return True

    def trim(self, seq: SequenceDescriptor, next_pos: int) -> int:
        """Free the blocks no query at ``next_pos`` or later can see (the
        oldest key it reads is ``next_pos - window + 1``; of a tumbling
        window, its window's first) → how many."""
        if not self.window or self.per_window:
            return 0
        first = getattr(seq, self._first)
        oldest = (next_pos // self.window * self.window if self.tumbling
                  else next_pos - self.window + 1)
        live = min(max(0, oldest // self.block_size), len(self.chain(seq)))
        if live <= first:
            return 0
        self.allocator.free(self.chain(seq)[first:live])
        setattr(seq, self._first, live)
        self.trimmed += live - first
        return live - first

    def take_slot(self, seq: SequenceDescriptor) -> bool:
        """Admission: the sequence's state slot, if one is free."""
        if self.slots is None or seq.state_slot >= 0:
            return True
        if not self.slots.free_slots:
            return False
        seq.state_slot = self.slots.take(seq.uid)
        return True

    @property
    def free_slots(self) -> int:
        return self.slots.free_slots if self.slots else 0

    def check_consistency(self) -> None:
        self.allocator.check_consistency()
        if self.slots is not None:
            self.slots.check_consistency()

    def drained(self) -> bool:
        """Every block and every state slot is back."""
        return (self.allocator.free_blocks == self.allocator.num_blocks
                and (self.slots is None
                     or self.slots.free_slots == self.slots.num_slots))

    def release(self, seq: SequenceDescriptor) -> None:
        if self.slots is not None and seq.state_slot >= 0:
            self.slots.give(seq.state_slot)
            seq.state_slot = -1
        self.allocator.free(self.chain(seq)[getattr(seq, self._first):])
        setattr(seq, self._blocks, [])
        setattr(seq, self._first, 0)
        self.reserved -= getattr(seq, self._reserved)
        setattr(seq, self._reserved, 0)


@dataclasses.dataclass(frozen=True)
class StepLayout:
    """Where each host input of a step lies in the ONE int32 buffer that
    takes them to the device (a step then makes one host-to-device copy, and
    ``programs.build_unpack`` takes the buffer apart there): ``fields`` are
    ``(name, first word, shape, dtype)`` in the buffer's order, ``size`` its
    words.  A float32 field lies in it bit for bit.  The layout follows what
    the engine was built with (rows, tokens a step, blocks a sequence, a
    second table, state slots, adapters) and nothing that changes later."""

    fields: Tuple[Tuple[str, int, Tuple[int, ...], str], ...]
    size: int

    @classmethod
    def of(cls, *fields) -> "StepLayout":
        """``fields``: ``(name, shape)`` or ``(name, shape, dtype)``, or None
        for one this engine does not have."""
        laid, at = [], 0
        for name, shape, *dtype in filter(None, fields):
            laid.append((name, at, tuple(shape), (dtype or ["int32"])[0]))
            at += math.prod(shape)
        return cls(tuple(laid), at)

    def new(self) -> np.ndarray:
        """A step's buffer, zeroed.  Fresh every step: what is on its way to
        the device is never written again."""
        return np.zeros(self.size, np.int32)

    def views(self, buf: np.ndarray) -> Dict[str, np.ndarray]:
        """Each field as an array that IS its words of ``buf``."""
        return {name: buf[at:at + math.prod(shape)].view(dtype).reshape(shape)
                for name, at, shape, dtype in self.fields}


def decode_layout(max_seqs: int, max_blocks_per_seq: int,
                  two_pools: bool = False, adapters: bool = False
                  ) -> StepLayout:
    """The decode step's: the program's arguments by their names, one row a
    sequence (``DecodeStateTable``'s rows)."""
    rows, table = (max_seqs,), (max_seqs, max_blocks_per_seq)
    return StepLayout.of(
        ("token_ids", rows), ("position_ids", rows), ("context_lens", rows),
        ("temps", rows, "float32"), ("seeds", rows),
        ("block_tables", table), ("win_tables", table) if two_pools else None,
        ("row_adapter", rows) if adapters else None)


def mixed_layout(max_tokens: int, max_seqs: int, max_blocks_per_seq: int,
                 two_pools: bool = False, state: bool = False,
                 adapters: bool = False) -> StepLayout:
    """The mixed step's: ``RaggedBatch``'s arrays."""
    toks, rows = (max_tokens,), (max_seqs,)
    table = (max_seqs, max_blocks_per_seq)
    return StepLayout.of(
        ("token_ids", toks), ("position_ids", toks), ("seq_index", toks),
        ("block_tables", table), ("win_tables", table) if two_pools else None,
        ("context_lens", rows), ("logits_rows", rows), ("chunk_start", rows),
        ("chunk_len", rows), ("state_slots", rows) if state else None,
        ("row_adapter", rows) if adapters else None)


@dataclasses.dataclass
class RaggedBatch:
    """One scheduled forward (reference ``RaggedBatchWrapper``): flattened
    tokens from every participating sequence + metadata the kernels need,
    padded to static shapes."""

    token_ids: np.ndarray  # (max_tokens,) int32
    position_ids: np.ndarray  # (max_tokens,) int32 — position within its seq
    seq_index: np.ndarray  # (max_tokens,) int32 — row in the block table
    block_tables: np.ndarray  # (max_seqs, max_blocks_per_seq) int32
    context_lens: np.ndarray  # (max_seqs,) int32 — tokens in cache AFTER this step
    logits_rows: np.ndarray  # (max_seqs,) int32 — flat index of each seq's last token
    chunk_start: np.ndarray  # (max_seqs,) int32 — abs pos of row's first token
    chunk_len: np.ndarray  # (max_seqs,) int32 — tokens scheduled for the row
    num_tokens: int
    num_seqs: int
    uids: List[int]
    #: the second pool's tables: the window layers' of a model with both kinds
    #: of layer, the window's of an EVA model
    win_tables: Optional[np.ndarray] = None
    #: a model with state layers: each row's state slot (unused rows: the
    #: scratch slot), (max_seqs,) int32
    state_slots: Optional[np.ndarray] = None
    #: with adapters: each row's slot of the adapter stack, (max_seqs,) int32
    row_adapter: Optional[np.ndarray] = None
    #: the one int32 buffer every array above is a view of
    #: (``RaggedBatchBuilder.layout``): what the step copies to the device
    packed: Optional[np.ndarray] = None


class DecodeStateTable:
    """Persistent SoA state for the pure-decode steady state.

    The reference walks ``SequenceDescriptor`` lists in the host loop every
    step (and so did we — VERDICT weak #7). Here decode bookkeeping lives in
    row-indexed numpy arrays updated with vectorized ops: dispatch inputs
    are THE arrays (no per-step rebuild), post-step updates touch Python
    only for sequences that just completed. Token history accumulates in a
    preallocated array and flushes into ``seq.tokens`` at retire."""

    def __init__(self, max_seqs: int, max_blocks_per_seq: int,
                 max_ctx: int, two_pools: bool = False,
                 main_grows: bool = False):
        self.max_seqs = max_seqs
        # whether the main pool is windowed: its chains then grow while the
        # sequence runs, and ``sync`` copies them again
        self.main_grows = main_grows
        self.block_tables = np.zeros((max_seqs, max_blocks_per_seq), np.int32)
        # the window layers' tables of a model with both kinds of layer
        self.win_tables = np.zeros_like(self.block_tables) \
            if two_pools else None
        self.ctx = np.zeros(max_seqs, np.int32)  # tokens already in cache
        self.next_tok = np.zeros(max_seqs, np.int32)  # next input token
        self.gen = np.zeros(max_seqs, np.int32)
        self.budget = np.zeros(max_seqs, np.int32)
        # lifetime KV reservation end: prompt + max_new_tokens.  Speculative
        # steps write k tokens past ctx; writes at pos >= limit must park in
        # the scratch block (the block table has no entry for them).
        self.limit = np.zeros(max_seqs, np.int32)
        self.active = np.zeros(max_seqs, bool)
        # per-row sampling state: temp < 0 means "inherit the step-level
        # scalar temperature" (requests that never set one)
        self.temp = np.full(max_seqs, -1.0, np.float32)
        self.seed = np.zeros(max_seqs, np.int32)
        # per-row adapter-stack slot (0 = null adapter, exact-zero delta)
        self.adapter = np.zeros(max_seqs, np.int32)
        self.hist = np.zeros((max_seqs, max_ctx), np.int32)
        self.hist_len = np.zeros(max_seqs, np.int32)
        self.row_of: Dict[int, int] = {}
        self.seq_at: Dict[int, SequenceDescriptor] = {}
        # ``seq_at``'s uids as an array (0: the row is free; uids start at 1)
        self.uid = np.zeros(max_seqs, np.int64)
        self._free = list(range(max_seqs - 1, -1, -1))

    def admit(self, seq: SequenceDescriptor) -> int:
        if seq.state_slot >= 0:  # a state slot IS the row (``StateSlots``)
            row = seq.state_slot
            self._free.remove(row)
        else:
            row = self._free.pop()
        self.row_of[seq.uid] = row
        self.seq_at[row] = seq
        self.uid[row] = seq.uid
        self.active[row] = True
        bt = self.block_tables[row]
        bt[:] = 0
        bt[:len(seq.blocks)] = seq.blocks
        if self.win_tables is not None:
            self.win_tables[row] = 0
        self.budget[row] = seq.max_new_tokens
        self.limit[row] = seq.cur_len + seq.max_new_tokens
        self.temp[row] = -1.0 if seq.temperature is None else seq.temperature
        self.seed[row] = np.int32(np.uint32(seq.seed & 0xFFFFFFFF))
        self.adapter[row] = seq.adapter_slot
        self.hist_len[row] = 0
        self.sync(seq)
        return row

    def sync(self, seq: SequenceDescriptor) -> None:
        """Refresh a row from its descriptor (after host-side prefill
        bookkeeping; the decode fast path never needs this)."""
        row = self.row_of[seq.uid]
        self.ctx[row] = seq.seen_tokens
        if seq.seen_tokens < seq.cur_len:
            self.next_tok[row] = seq.tokens[seq.seen_tokens]
        self.gen[row] = seq.generated
        # chains that grow while the sequence runs (a windowed pool's)
        if self.main_grows:
            self.block_tables[row, :len(seq.blocks)] = seq.blocks
        if self.win_tables is not None:
            self.win_tables[row, :len(seq.win_blocks)] = seq.win_blocks

    def flush_tokens(self, seq: SequenceDescriptor) -> None:
        """Append the row's accumulated decode history to ``seq.tokens``."""
        row = self.row_of[seq.uid]
        n = int(self.hist_len[row])
        if n:
            seq.tokens.extend(self.hist[row, :n].tolist())
            seq.generated = int(self.gen[row])
            seq.seen_tokens = int(self.ctx[row])
            self.hist_len[row] = 0

    def retire(self, seq: SequenceDescriptor) -> None:
        self.flush_tokens(seq)
        row = self.row_of.pop(seq.uid)
        del self.seq_at[row]
        self.uid[row] = 0
        self.active[row] = False
        self.ctx[row] = 0
        self.next_tok[row] = 0
        self.gen[row] = 0
        self.limit[row] = 0
        self.temp[row] = -1.0
        self.seed[row] = 0
        self.adapter[row] = 0
        self.hist_len[row] = 0
        self._free.append(row)


class RaggedBatchBuilder:
    def __init__(self, max_tokens: int, max_seqs: int, max_blocks_per_seq: int,
                 two_pools: bool = False, state_scratch: int = -1,
                 adapters: bool = False):
        self.max_tokens = max_tokens
        self.max_seqs = max_seqs
        self.max_blocks_per_seq = max_blocks_per_seq
        # a model with state layers: the scratch slot unused rows point at
        self.state_scratch = state_scratch
        self.layout = mixed_layout(max_tokens, max_seqs, max_blocks_per_seq,
                                   two_pools, state_scratch >= 0, adapters)

    def build(self, seqs: List[Tuple[SequenceDescriptor, int]]) -> RaggedBatch:
        """seqs: (descriptor, n_new_tokens) pairs already capacity-checked.
        The batch's arrays are filled where they lie in its one buffer."""
        if len(seqs) > self.max_seqs:
            raise ValueError(f"{len(seqs)} sequences > max_seqs {self.max_seqs}")
        packed = self.layout.new()
        # the layout's fields are the batch's arrays, by name
        b = RaggedBatch(**self.layout.views(packed), num_tokens=0,
                        num_seqs=len(seqs), uids=[], packed=packed)
        b.seq_index[:] = -1
        if b.state_slots is not None:
            b.state_slots[:] = self.state_scratch
        cursor = 0
        for row, (seq, n_new) in enumerate(seqs):
            start = seq.seen_tokens
            new_tokens = seq.tokens[start:start + n_new]
            if cursor + len(new_tokens) > self.max_tokens:
                raise ValueError("ragged batch token budget exceeded")
            sl = slice(cursor, cursor + len(new_tokens))
            b.token_ids[sl] = new_tokens
            b.position_ids[sl] = np.arange(start, start + len(new_tokens))
            b.seq_index[sl] = row
            b.block_tables[row, :len(seq.blocks)] = seq.blocks
            if b.win_tables is not None:
                b.win_tables[row, :len(seq.win_blocks)] = seq.win_blocks
            b.context_lens[row] = start + len(new_tokens)
            b.logits_rows[row] = cursor + len(new_tokens) - 1
            b.chunk_start[row] = start
            b.chunk_len[row] = len(new_tokens)
            if b.state_slots is not None:
                b.state_slots[row] = seq.state_slot
            if b.row_adapter is not None:
                b.row_adapter[row] = seq.adapter_slot
            cursor += len(new_tokens)
            b.uids.append(seq.uid)
        b.num_tokens = cursor
        return b

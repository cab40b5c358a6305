"""What sequences hold on the device: the paged KV-cache block pool, the
pool of fixed-size states of a model without keys and values (``StatePool``),
the two behind one ledger for a model with both (``HybridPool``) and two
block pools behind one ledger for a model whose layers are of two KINDS, full
and window attention (``LayerTypedPool``, at the end) — static-shape JAX
storage, host-side ledger.

vLLM-style paging on the TPU shape discipline: the device side is two
fixed arrays per model

    k, v : (layers, num_blocks, heads, block_size, head_dim)

allocated ONCE at engine start (no reallocation, no ragged shapes — the
decode step jits once and every admission/eviction pattern reuses it).
The host side is a free-list ledger mapping sequence ids to the physical
blocks they own; block tables (logical→physical per sequence, padded
with the reserved trash block) are plain int32 numpy rows the engine
stacks into the decode step's ``(slots, tmax)`` operand.

Block 0 is RESERVED as the trash block: inactive decode slots and
padded prefill positions scatter their k/v there, so masked lanes never
corrupt live cache and the jitted step needs no data-dependent control
flow.  Eviction under pressure is mechanism here (``free`` returns a
sequence's blocks), policy in ``llm.scheduler`` (preempt-youngest,
recompute on re-admission).

Sharing (``llm.prefix_cache``): every allocated block carries a
REFERENCE COUNT — one per owning sequence plus one while the prefix
tree retains it (``cache_retain``/``cache_release``).  ``allocate`` can
seed a sequence's table with already-resident ``shared`` blocks (the
matched prefix), and a block returns to the free list only when its
count reaches zero.  A block whose only reference is the cache's is
*evictable* — reclaimable capacity the scheduler drains before it
preempts live requests.  Copy-on-write is split: the LEDGER fork (a
fresh exclusive block for the divergent tail) happens here, the device
copy in ``model_runner.fork_blocks``.  Shared blocks are read-only by
construction — prefill starts past the matched prefix and decode writes
only at the sequence tail, so no jitted step ever scatters into a
position a shared block covers.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Pool geometry. ``num_blocks`` INCLUDES the reserved trash block, so
    usable capacity is ``num_blocks - 1`` blocks of ``block_size`` tokens.
    ``max_blocks_per_seq`` fixes the block-table width (tmax) — it caps a
    single sequence's length at ``max_blocks_per_seq * block_size``."""

    num_blocks: int = 128
    block_size: int = 16
    max_blocks_per_seq: int = 32

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        if self.block_size < 1 or self.max_blocks_per_seq < 1:
            raise ValueError("block_size and max_blocks_per_seq must be >= 1")

    @property
    def max_seq_len(self) -> int:
        return self.max_blocks_per_seq * self.block_size


class KVBlockPool:
    """The pool: device arrays + thread-safe host ledger.

    Device arrays are plain attributes (``k``, ``v``) the engine threads
    through its jitted step functions and writes back — functional
    updates, the pool object just holds the current version.
    """

    #: sequences own blocks that grow, and blocks are shared: the prefix
    #: cache, speculative windows and head-sharding are built on that
    paged = True

    def __init__(
        self,
        cfg: CacheConfig,
        n_layers: int,
        n_heads: int,
        head_dim: int,
        dtype="float32",
        sharding=None,
        values: bool = True,
    ):
        import jax.numpy as jnp

        self.cfg = cfg
        shape = (n_layers, cfg.num_blocks, n_heads, cfg.block_size, head_dim)
        # multichip passes the head-sharded placement over the tp mesh:
        # every device allocates only ITS shard (the whole pool never
        # exists on one device) and the engine's jitted steps never move
        # it; the host ledger below is unchanged — block ids are global,
        # every device holds the same blocks' local heads
        self.k = jnp.zeros(shape, jnp.dtype(dtype), device=sharding)
        # ``values=False``: a block holds ONE array, whatever the family
        # says a token leaves behind (``models.kimi_k2``: a latent row that
        # is key and value at once); the ledger below does not care
        self.v = jnp.zeros(shape, jnp.dtype(dtype), device=sharding) if values else None
        self._lock = threading.Lock()
        # LIFO free list of physical block ids; 0 reserved (trash)
        self._free = list(range(cfg.num_blocks - 1, 0, -1))
        self._owned: dict[str, list[int]] = {}
        # reference counts for every non-free block: one per owning
        # sequence + one while the prefix tree retains it; a block is
        # freed only at zero (llm.prefix_cache shares blocks across
        # sequences, so ownership alone no longer implies exclusivity)
        self._ref: dict[int, int] = {}
        self._cache_held: set[int] = set()

    @staticmethod
    def n_arrays(values: bool = True, **_layout) -> int:
        """How many device arrays a pool of this layout holds (a step
        program's donated arguments are counted before there is a pool)."""
        return 2 if values else 1

    @property
    def arrays(self) -> tuple:
        """The device arrays a jitted step takes (donated) and hands back."""
        return (self.k,) if self.v is None else (self.k, self.v)

    @arrays.setter
    def arrays(self, new) -> None:
        self.k, self.v = new if self.v is not None else (*new, None)

    # -- capacity ----------------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.cfg.block_size)

    @property
    def block_bytes(self) -> int:
        """Device bytes ONE physical block occupies across both pool
        arrays and every layer (k + v) — the unit the HBM ledger gauges
        multiply block counts by."""
        return self.device_bytes // self.cfg.num_blocks

    @property
    def device_bytes(self) -> int:
        """Total device footprint of the pool arrays (k + v), trash
        block included — allocated once at engine start, never resized."""
        return sum(a.nbytes for a in self.arrays)

    @property
    def num_free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_used_blocks(self) -> int:
        """DISTINCT blocks referenced by at least one sequence (a block
        shared by N sequences counts once; cache-only residents count
        zero — they are reclaimable, not in use)."""
        with self._lock:
            return len({b for bs in self._owned.values() for b in bs})

    @property
    def num_cached_blocks(self) -> int:
        with self._lock:
            return len(self._cache_held)

    @property
    def num_evictable_blocks(self) -> int:
        """Blocks whose ONLY reference is the prefix cache's — capacity
        the scheduler can reclaim without preempting anyone."""
        with self._lock:
            return sum(1 for b in self._cache_held if self._ref.get(b) == 1)

    def ledger_counts(self) -> dict:
        """One consistent snapshot of the block partition for the HBM
        ledger gauges (a single lock acquisition — the per-property reads
        could interleave with an allocation between them): ``free`` +
        ``seq_owned`` (distinct blocks owned by ≥1 sequence, shared or
        not) + ``cache_only`` (resident purely for the prefix tree)
        partition the usable blocks, the same invariant ``audit()``
        checks."""
        with self._lock:
            owned = {b for bs in self._owned.values() for b in bs}
            return {
                "free": len(self._free),
                "seq_owned": len(owned),
                "cache_only": len(self._cache_held - owned),
            }

    def utilization(self) -> float:
        """Fraction of usable (non-reserved) blocks currently owned by
        live sequences.  Cache-only blocks are excluded on purpose: they
        are evictable on demand, and counting them would page the
        kv-pool-exhaustion SLO on a healthy warm cache."""
        usable = self.cfg.num_blocks - 1
        return self.num_used_blocks / max(usable, 1)

    def can_allocate(self, n_tokens: int, shared: int = 0) -> bool:
        """True when a fresh allocation for ``n_tokens`` fits, with the
        first ``shared`` blocks coming from the prefix cache (only the
        remainder needs the free list)."""
        need = self.blocks_for(n_tokens)
        if need > self.cfg.max_blocks_per_seq:
            return False
        with self._lock:
            return need - shared <= len(self._free)

    # -- ledger ------------------------------------------------------------

    def allocate(
        self, seq_id: str, n_tokens: int, shared: Sequence[int] = ()
    ) -> list[int]:
        """Claim enough blocks for ``n_tokens``; raises if the sequence
        already owns blocks, exceeds the table width, or the pool is dry
        (callers check ``can_allocate`` / preempt first).

        ``shared`` — already-resident cache blocks forming the head of
        the table (the matched prefix, in prompt order): each gains a
        reference instead of leaving the free list.  Only the remainder
        is drawn fresh.  All-or-nothing: validation precedes any
        mutation, so a failed allocate changes no counts."""
        need = self.blocks_for(n_tokens)
        shared = list(shared)
        with self._lock:
            if seq_id in self._owned:
                raise ValueError(f"sequence {seq_id!r} already owns blocks")
            if need > self.cfg.max_blocks_per_seq:
                raise ValueError(
                    f"{n_tokens} tokens need {need} blocks > "
                    f"max_blocks_per_seq={self.cfg.max_blocks_per_seq}"
                )
            if len(shared) >= need and shared:
                raise ValueError(
                    f"{len(shared)} shared blocks >= {need} needed: the "
                    "tail block must be exclusive (prefill writes there)"
                )
            for b in shared:
                if b not in self._cache_held or self._ref.get(b, 0) < 1:
                    raise ValueError(
                        f"shared block {b} is not cache-resident"
                    )
            fresh = need - len(shared)
            if fresh > len(self._free):
                raise MemoryError(
                    f"paged KV pool exhausted: need {fresh} blocks, "
                    f"{len(self._free)} free"
                )
            for b in shared:
                self._ref[b] += 1
            new = [self._free.pop() for _ in range(fresh)]
            for b in new:
                self._ref[b] = 1
            blocks = shared + new
            self._owned[seq_id] = blocks
            return list(blocks)

    def grow_to(self, seq_id: str, n_tokens: int) -> bool:
        """Ensure ``seq_id`` owns enough blocks for ``n_tokens``.  Returns
        False (allocation unchanged) when the pool can't cover the growth —
        the scheduler then evicts someone and retries."""
        with self._lock:
            blocks = self._owned.get(seq_id)
            if blocks is None:
                raise KeyError(f"unknown sequence {seq_id!r}")
            need = self.blocks_for(n_tokens)
            if need > self.cfg.max_blocks_per_seq:
                return False
            extra = need - len(blocks)
            if extra <= 0:
                return True
            if extra > len(self._free):
                return False
            for _ in range(extra):
                b = self._free.pop()
                self._ref[b] = 1
                blocks.append(b)
            return True

    def _deref_locked(self, block: int) -> bool:
        """Drop one reference (lock held); returns True when the block
        actually hit zero and went back to the free list."""
        n = self._ref.get(block, 0) - 1
        if n > 0:
            self._ref[block] = n
            return False
        self._ref.pop(block, None)
        self._cache_held.discard(block)
        self._free.append(block)
        return True

    def shrink_to(self, seq_id: str, n_tokens: int) -> int:
        """Return the sequence's TAIL blocks beyond what ``n_tokens`` needs
        to the free list; returns the number released.  The speculative-
        decode rollback: verification provisionally grows a sequence by
        ``k`` positions, and the rejected tail's blocks come back here.
        (The device-side k/v of rejected positions need no rollback — they
        sit beyond the sequence's length, every attention path masks by
        length, and the next window overwrites them before the length ever
        reaches them.)"""
        with self._lock:
            blocks = self._owned.get(seq_id)
            if blocks is None:
                raise KeyError(f"unknown sequence {seq_id!r}")
            keep = self.blocks_for(n_tokens)
            excess = len(blocks) - keep
            if excess <= 0:
                return 0
            tail = blocks[keep:]
            del blocks[keep:]
            for b in reversed(tail):
                self._deref_locked(b)
            return excess

    def release_head(self, seq_id: str, n_blocks: int) -> int:
        """Return the sequence's FIRST ``n_blocks`` blocks to the free list
        (``shrink_to``'s mirror); the rest keep their order.  What a window
        layer's pool does with the blocks behind the window
        (``LayerTypedPool``, which keeps count of how many went).  Returns
        how many went."""
        with self._lock:
            blocks = self._owned.get(seq_id)
            if blocks is None:
                raise KeyError(f"unknown sequence {seq_id!r}")
            head = blocks[:max(n_blocks, 0)]
            del blocks[:len(head)]
            for b in head:
                self._deref_locked(b)
            return len(head)

    def free(self, seq_id: str) -> int:
        """Drop the sequence's references (idempotent); returns how many
        blocks actually reached zero and returned to the free list
        (shared/cached blocks survive on their remaining references)."""
        with self._lock:
            blocks = self._owned.pop(seq_id, None)
            if not blocks:
                return 0
            return sum(1 for b in reversed(blocks) if self._deref_locked(b))

    def owner_count(self) -> int:
        with self._lock:
            return len(self._owned)

    def blocks_of(self, seq_id: str) -> list[int]:
        """Copy of the sequence's block list (table order)."""
        with self._lock:
            blocks = self._owned.get(seq_id)
            if blocks is None:
                raise KeyError(f"unknown sequence {seq_id!r}")
            return list(blocks)

    # -- prefix-cache residency (llm.prefix_cache) -------------------------

    def cache_retain(self, block: int) -> bool:
        """Take the prefix tree's reference on an allocated block (False
        if the block is free/unknown — a freed block cannot resurrect, or
        already retained — one tree node per block)."""
        with self._lock:
            if block not in self._ref or block in self._cache_held:
                return False
            self._cache_held.add(block)
            self._ref[block] += 1
            return True

    def cache_release(self, block: int) -> bool:
        """Drop the prefix tree's reference (eviction/flush); frees the
        block when no sequence still holds it."""
        with self._lock:
            if block not in self._cache_held:
                return False
            self._cache_held.discard(block)
            return self._deref_locked(block)

    def ref(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    def is_cache_held(self, block: int) -> bool:
        with self._lock:
            return block in self._cache_held

    def is_evictable(self, block: int) -> bool:
        """Only the cache references it: reclaimable without preemption."""
        with self._lock:
            return block in self._cache_held and self._ref.get(block) == 1

    def cache_held_blocks(self) -> set:
        with self._lock:
            return set(self._cache_held)

    def audit(self) -> dict:
        """Free-list ledger invariant check (the watchdog's leak audit):
        free + exclusively-owned + shared-with-refcount + cache-only must
        still PARTITION the usable blocks, every id must be in range, and
        every refcount must equal its observable references (#owning
        sequences + 1 if cache-held).  Runs under the pool lock alone —
        safe while the engine lock is wedged.  Returns counts plus the
        owner ids so the caller can cross-check owners against live
        requests (and the prefix tree via ``PrefixCache.audit``)."""
        with self._lock:
            free = list(self._free)
            owned = {k: list(v) for k, v in self._owned.items()}
            cache_held = set(self._cache_held)
            ref = dict(self._ref)
        usable = self.cfg.num_blocks - 1
        owner_count: dict[int, int] = {}
        for bs in owned.values():
            for b in bs:
                owner_count[b] = owner_count.get(b, 0) + 1
        live = set(owner_count) | cache_held
        # a shared block appears ONCE in the live set — the partition is
        # over distinct blocks, the sharing is what the refcounts carry
        all_blocks = free + sorted(live)
        duplicates = len(all_blocks) != len(set(all_blocks))
        out_of_range = sum(
            1 for b in all_blocks if not (1 <= b < self.cfg.num_blocks)
        )
        missing = usable - len(all_blocks)
        ref_errors = sum(
            1
            for b in live
            if ref.get(b, 0)
            != owner_count.get(b, 0) + (1 if b in cache_held else 0)
        ) + sum(1 for b in ref if b not in live)
        return {
            "ok": not duplicates and not out_of_range and missing == 0
            and ref_errors == 0,
            "free": len(free),
            "owned": len(owner_count),
            "owners": list(owned),
            "shared": sum(1 for n in owner_count.values() if n > 1),
            "cached": len(cache_held),
            "cached_only": sum(
                1 for b in cache_held if b not in owner_count
            ),
            "ref_errors": ref_errors,
            "missing": missing,          # >0 leaked, <0 double-counted
            "duplicates": duplicates,
            "out_of_range": out_of_range,
        }

    def table_row(self, seq_id: Optional[str]) -> np.ndarray:
        """(max_blocks_per_seq,) int32 block table, padded with the trash
        block.  ``None`` (an inactive slot) is all-trash."""
        row = np.zeros(self.cfg.max_blocks_per_seq, np.int32)
        if seq_id is not None:
            with self._lock:
                blocks = self._owned.get(seq_id)
                if blocks is None:
                    raise KeyError(f"unknown sequence {seq_id!r}")
                row[: len(blocks)] = blocks
        return row


@dataclasses.dataclass(frozen=True)
class StateConfig:
    """Geometry of a ``StatePool``, in the words the engine and the
    scheduler use of a paged pool: a sequence's whole state is ONE block
    that holds ``max_seq_len`` tokens, so nothing grows and the length
    limit is the model's positions.  ``num_blocks`` is one more than the
    slots, which keeps the callers' ``num_blocks - 1`` usable blocks true.
    ``trash``: slot 0 of the arrays is reserved, as block 0 of a paged pool
    is, for the steps of a model whose dead decode rows and padded chunk
    rows write SOMEWHERE (a ring of K/V); sequences then own slots 1 ..
    ``slots``.  Without it no state is reserved (a dead decode row writes
    nothing)."""

    slots: int
    max_seq_len: int
    trash: bool = False

    def __post_init__(self):
        if self.slots < 1 or self.max_seq_len < 1:
            raise ValueError("slots and max_seq_len must be >= 1")

    @property
    def block_size(self) -> int:
        return self.max_seq_len

    @property
    def max_blocks_per_seq(self) -> int:
        return 1

    @property
    def num_blocks(self) -> int:
        return self.slots + 1


class StatePool:
    """Fixed-size state of a sequence, a slot each: device arrays
    ``(layers, slots) + shape`` allocated at engine start, one a KIND of
    state (``leaves``: name -> (layers, one slot's shape, dtype); a model
    with one kind gives ``n_layers, state_shape, dtype`` and the leaf is
    ``state``), and the ledger calls the scheduler and the engine make of
    ``KVBlockPool``.  A sequence owns one slot, the same in every leaf,
    from admission to its end: admission needs a free slot, nothing grows,
    nothing is shared, nobody is preempted for memory.  The jitted steps
    take the leaves donated and hand them back; a slot's state is
    overwritten by its next owner's first prefill chunk, never cleared."""

    paged = False

    def __init__(self, cfg: StateConfig, n_layers: int = 0, state_shape: tuple = (),
                 dtype="float32", leaves: Optional[dict] = None):
        import jax.numpy as jnp

        self.cfg = cfg
        if leaves is None:
            leaves = {"state": (n_layers, state_shape, dtype)}
        self._first = int(cfg.trash)
        self.leaves = {
            name: jnp.zeros((layers, cfg.slots + self._first) + tuple(shape), jnp.dtype(dt))
            for name, (layers, shape, dt) in leaves.items()
        }
        self._lock = threading.Lock()
        self._free = list(range(cfg.slots - 1 + self._first, self._first - 1, -1))  # LIFO
        self._owned: dict[str, int] = {}

    @property
    def state(self):
        """The one leaf of a pool made from ``n_layers, state_shape``."""
        return self.leaves["state"]

    @property
    def arrays(self) -> tuple:
        return tuple(self.leaves.values())

    @arrays.setter
    def arrays(self, new) -> None:
        self.leaves = dict(zip(self.leaves, new, strict=True))

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.cfg.max_seq_len)

    @property
    def block_bytes(self) -> int:
        """Device bytes of one slot's state across every leaf and layer."""
        return self.device_bytes // (self.cfg.slots + self._first)

    @property
    def device_bytes(self) -> int:
        return sum(a.nbytes for a in self.leaves.values())

    def leaf_bytes(self) -> dict:
        """name -> device bytes of that kind of state, every slot."""
        return {name: a.nbytes for name, a in self.leaves.items()}

    @property
    def num_free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_used_blocks(self) -> int:
        with self._lock:
            return len(self._owned)

    #: nothing is shared, so nothing is held for a cache
    num_evictable_blocks = 0

    def ledger_counts(self) -> dict:
        with self._lock:
            return {"free": len(self._free), "seq_owned": len(self._owned),
                    "cache_only": 0}

    def utilization(self) -> float:
        return self.num_used_blocks / self.cfg.slots

    def can_allocate(self, n_tokens: int, shared: int = 0) -> bool:
        if self.blocks_for(n_tokens) > 1:
            return False
        with self._lock:
            return bool(self._free)

    def allocate(self, seq_id: str, n_tokens: int, shared: Sequence[int] = ()) -> list[int]:
        """Claim a slot; all-or-nothing like ``KVBlockPool.allocate``."""
        with self._lock:
            if seq_id in self._owned:
                raise ValueError(f"sequence {seq_id!r} already owns a state")
            if shared:
                raise ValueError("a recurrent state is never shared")
            if self.blocks_for(n_tokens) > 1:
                raise ValueError(
                    f"{n_tokens} tokens exceed the model's {self.cfg.max_seq_len} positions")
            if not self._free:
                raise MemoryError("state pool exhausted: no free slot")
            slot = self._owned[seq_id] = self._free.pop()
            return [slot]

    def grow_to(self, seq_id: str, n_tokens: int) -> bool:
        """A state holds any length up to the model's: nothing to grow."""
        with self._lock:
            if seq_id not in self._owned:
                raise KeyError(f"unknown sequence {seq_id!r}")
        return self.blocks_for(n_tokens) <= 1

    def free(self, seq_id: str) -> int:
        with self._lock:
            slot = self._owned.pop(seq_id, None)
            if slot is None:
                return 0
            self._free.append(slot)
            return 1

    def blocks_of(self, seq_id: str) -> list[int]:
        with self._lock:
            if seq_id not in self._owned:
                raise KeyError(f"unknown sequence {seq_id!r}")
            return [self._owned[seq_id]]

    def audit(self) -> dict:
        """Free and owned slots must partition the pool, each id in range
        and held once; the keys the watchdog reads of ``KVBlockPool.audit``."""
        with self._lock:
            free, owned = list(self._free), dict(self._owned)
        held = free + list(owned.values())
        duplicates = len(held) != len(set(held))
        out_of_range = sum(
            1 for s in held if not self._first <= s < self.cfg.slots + self._first)
        missing = self.cfg.slots - len(held)
        return {
            "ok": not duplicates and not out_of_range and missing == 0,
            "free": len(free), "owned": len(owned), "owners": list(owned),
            "shared": 0, "cached": 0, "cached_only": 0, "ref_errors": 0,
            "missing": missing, "duplicates": duplicates,
            "out_of_range": out_of_range,
        }

    def table_row(self, seq_id: Optional[str]) -> np.ndarray:
        """(1,) int32: the sequence's slot; ``None`` (an empty decode row)
        is 0: the trash slot where the pool has one, else a slot that a
        dead row does not touch whatever it names."""
        row = np.zeros(1, np.int32)
        if seq_id is not None:
            row[0] = self.blocks_of(seq_id)[0]
        return row


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Geometry of a ``HybridPool``: ``CacheConfig``'s for the blocks, and
    the slots of state beside them."""

    num_blocks: int
    block_size: int
    max_blocks_per_seq: int
    slots: int

    @property
    def max_seq_len(self) -> int:
        return self.max_blocks_per_seq * self.block_size


class HybridPool:
    """Two kinds of cache for one sequence behind ONE ledger: growing
    blocks of K/V (``kv``, a ``KVBlockPool``) and a slot of fixed-size
    state (``states``, a ``StatePool`` with a trash slot).  It answers the
    calls the scheduler and the engine make of ``KVBlockPool``: a sequence
    is admitted when a slot AND its blocks are free, grows by blocks, is
    preempted for blocks (recompute: its next first chunk overwrites its
    new slot), and a free returns both.  Block counts, ``block_bytes`` and
    the free / owned partition are the K/V pool's, where the pressure is;
    the slots stand beside them (``ledger_counts``, ``audit``).  The K/V pool
    has the layers the family's ``kv_layout()`` gives it (ONE shared layer:
    ``models.phi4flash``; every layer's: ``models.falcon_h1``; the attention
    layers of a pattern, the state's leaves counting the others:
    ``models.granite_h``), and a block's bytes are its rows in all of them.

    A table row is ``[slot, block table...]`` and ``arrays`` the K/V pool's
    two followed by the state's leaves, in the order the model's steps take
    them.  It has no lock of its own: each part's is enough, because the
    calls that change both (``allocate``, ``free``) come from under the
    engine's lock, and ``audit`` reads the parts until they agree."""

    paged = True

    def __init__(self, cfg: HybridConfig, kv_layout: dict, leaves: dict):
        self.cfg = cfg
        self.kv = KVBlockPool(
            CacheConfig(cfg.num_blocks, cfg.block_size, cfg.max_blocks_per_seq), **kv_layout)
        self.states = StatePool(
            StateConfig(cfg.slots, cfg.max_seq_len, trash=True), leaves=leaves)

    @property
    def k(self):
        return self.kv.k

    @property
    def arrays(self) -> tuple:
        return self.kv.arrays + self.states.arrays

    @arrays.setter
    def arrays(self, new) -> None:
        self.kv.arrays, self.states.arrays = new[:2], new[2:]

    def blocks_for(self, n_tokens: int) -> int:
        return self.kv.blocks_for(n_tokens)

    @property
    def block_bytes(self) -> int:
        return self.kv.block_bytes

    @property
    def device_bytes(self) -> int:
        return self.kv.device_bytes + self.states.device_bytes

    @property
    def num_free_blocks(self) -> int:
        return self.kv.num_free_blocks

    @property
    def num_used_blocks(self) -> int:
        return self.kv.num_used_blocks

    num_evictable_blocks = 0

    def ledger_counts(self) -> dict:
        slots = self.states.ledger_counts()
        return dict(self.kv.ledger_counts(), slots_free=slots["free"],
                    slots_owned=slots["seq_owned"])

    def utilization(self) -> float:
        return self.kv.utilization()

    def can_allocate(self, n_tokens: int, shared: int = 0) -> bool:
        return self.states.num_free_blocks > 0 and self.kv.can_allocate(n_tokens, shared)

    def allocate(self, seq_id: str, n_tokens: int, shared: Sequence[int] = ()) -> list[int]:
        """Claim a slot and the blocks for ``n_tokens``, or neither."""
        if shared:
            raise ValueError("a sequence with a state shares no blocks")
        self.states.allocate(seq_id, 1)
        try:
            # the ledger entry is the caller's, under ``seq_id``, exactly as
            # KVBlockPool.allocate's own: this only forwards it
            return self.kv.allocate(seq_id, n_tokens)  # raylint: disable=RL015
        except Exception:
            self.states.free(seq_id)
            raise

    def grow_to(self, seq_id: str, n_tokens: int) -> bool:
        return self.kv.grow_to(seq_id, n_tokens)

    def free(self, seq_id: str) -> int:
        freed = self.kv.free(seq_id)
        self.states.free(seq_id)
        return freed

    def blocks_of(self, seq_id: str) -> list[int]:
        return self.kv.blocks_of(seq_id)

    def audit(self) -> dict:
        """Both parts' audits, and every owner holding a slot AND blocks:
        the K/V pool's keys, ``slots`` the state pool's, ``unpaired`` the
        owners of one and not the other (read again once: an ``allocate``
        in flight holds its slot before its blocks)."""
        for _ in range(2):
            kv, slots = self.kv.audit(), self.states.audit()
            unpaired = sorted(set(kv["owners"]) ^ set(slots["owners"]))
            if not unpaired:
                break
        return dict(kv, ok=kv["ok"] and slots["ok"] and not unpaired,
                    slots=slots, unpaired=unpaired)

    def table_row(self, seq_id: Optional[str]) -> np.ndarray:
        """(1 + max_blocks_per_seq,) int32: the slot, then the block table;
        ``None`` is the trash slot and all trash blocks."""
        return np.concatenate([self.states.table_row(seq_id), self.kv.table_row(seq_id)])


@dataclasses.dataclass(frozen=True)
class LayerTypedConfig:
    """Geometry of a ``LayerTypedPool``: ``CacheConfig``'s for the FULL
    layers' blocks (what the scheduler and the engine read), and beside it
    the window layers': ``window`` tokens a query sees, ``chunk`` tokens the
    longest step writes of one sequence, ``slots`` sequences at once."""

    num_blocks: int
    block_size: int
    max_blocks_per_seq: int
    window: int
    chunk: int
    slots: int

    def __post_init__(self):
        if self.window < 1 or self.chunk < 1 or self.slots < 1:
            raise ValueError("window, chunk and slots must be >= 1")

    @property
    def max_seq_len(self) -> int:
        return self.max_blocks_per_seq * self.block_size

    @property
    def window_blocks_per_seq(self) -> int:
        """The most window blocks a sequence holds: those that ``window - 1``
        keys before a step's first query and its ``chunk`` positions touch
        (``W / BS + chunk / BS + 1`` where the block size divides both), and
        never more than its table has."""
        span = self.window - 1 + self.chunk
        return min(-(-span // self.block_size) + 1, self.max_blocks_per_seq)

    @property
    def window_num_blocks(self) -> int:
        """The window sub-pool: every slot's most, and the trash block."""
        return self.slots * self.window_blocks_per_seq + 1


class LayerTypedPool:
    """Blocks of TWO layer kinds for one sequence behind ONE ledger: the
    ``full`` attention layers' (a ``KVBlockPool`` that holds every block of
    a sequence, as any) and the ``window`` layers' (a second ``KVBlockPool``
    with its own arrays and free list), in which a sequence holds only the
    blocks a later query can still see.  It answers the calls the scheduler
    and the engine make of ``KVBlockPool`` the way ``HybridPool`` does, by
    composition: block counts, ``blocks_of``, ``block_bytes`` and the free /
    owned partition are the FULL sub-pool's, where the pressure is; the
    window sub-pool stands beside them (``ledger_counts``, ``audit``,
    ``stats``).

    **The window layers' blocks.**  A window block is claimed when a step is
    about to write into it and handed back to the free list when it lies
    WHOLLY behind the window of the next step's first query
    (``ops.paged_attention.first_window_block``, the rule the kernels start
    their walk by).  ``slide(seq, start, n)`` does both for a step that
    writes positions ``start .. start + n`` with its first query at
    ``start``; ``grow_to(seq, n_tokens)`` is that for a decode (one query at
    ``n_tokens - 1``) after the full layers' growth, and the engine calls
    ``slide`` itself before each prefill chunk (the full layers' blocks of a
    whole prompt are claimed at admission; a window layer's could not be: 262
    against 37 at 32k tokens).  A sequence therefore holds at most
    ``window_blocks_per_seq`` window blocks, the sub-pool has that many for
    every slot, and a window claim NEVER fails: admission, growth and
    preemption are decided by the full layers' blocks alone, exactly as in a
    ``KVBlockPool``.

    **Why releasing at launch is enough.**  ``slide`` runs on the host while
    the step that needs it is being built, and steps ahead of it may still
    be in flight on the device, reading a block this call hands back.  The
    block is written again only by a step launched LATER (this one at the
    earliest), and every step takes the pool's arrays donated from the step
    before: the device runs them in launch order, so whoever re-claims the
    block writes it after every earlier reader is done.  What matters is
    that no step YET TO BE LAUNCHED reads a released block, and none does:
    its queries stand at ``start`` or later, the table it is sent has the
    trash block in the released entries, and the kernels never read an entry
    before their first visible block.

    A table row is the full layers' table followed by the window layers',
    both ``max_blocks_per_seq`` wide and indexed by LOGICAL block, a
    released or never-claimed entry the trash block; ``arrays`` is ``(k_full,
    v_full, k_window, v_window)``.  Nothing is shared: the radix prefix
    cache keys blocks of ONE kind (the engine refuses it for this pool).
    No lock of its own beside ``_first``'s: the calls that change both parts
    come from under the engine's lock, as ``HybridPool``'s."""

    paged = True

    def __init__(self, cfg: LayerTypedConfig, kv_layout: dict):
        self.cfg = cfg
        layout = {k: v for k, v in kv_layout.items() if k not in ("kinds", "window")}
        kinds = kv_layout["kinds"]
        self.full = KVBlockPool(
            CacheConfig(cfg.num_blocks, cfg.block_size, cfg.max_blocks_per_seq),
            n_layers=kinds["full"], **layout)
        self.windowed = KVBlockPool(
            CacheConfig(cfg.window_num_blocks, cfg.block_size, cfg.max_blocks_per_seq),
            n_layers=kinds["window"], **layout)
        self._lock = threading.Lock()
        #: logical index of the first window block each sequence still holds
        self._first: dict[str, int] = {}
        self._released = 0

    @staticmethod
    def n_arrays(**_layout) -> int:
        return 4

    @property
    def k(self):
        return self.full.k

    @property
    def arrays(self) -> tuple:
        return self.full.arrays + self.windowed.arrays

    @arrays.setter
    def arrays(self, new) -> None:
        self.full.arrays, self.windowed.arrays = new[:2], new[2:]

    def blocks_for(self, n_tokens: int) -> int:
        return self.full.blocks_for(n_tokens)

    @property
    def block_bytes(self) -> int:
        """Device bytes of one FULL-layer block (the unit ``seq_owned`` and
        ``free`` count); a window block's are ``window_block_bytes``."""
        return self.full.block_bytes

    @property
    def window_block_bytes(self) -> int:
        return self.windowed.block_bytes

    @property
    def device_bytes(self) -> int:
        return self.full.device_bytes + self.windowed.device_bytes

    @property
    def num_free_blocks(self) -> int:
        return self.full.num_free_blocks

    @property
    def num_used_blocks(self) -> int:
        return self.full.num_used_blocks

    num_evictable_blocks = 0

    def ledger_counts(self) -> dict:
        """The full layers' partition (``KVBlockPool.ledger_counts``), and
        beside it what the sequences hold NOW of each kind, the window
        sub-pool's free blocks and what the window layers have handed back
        behind a window since the start (one lock each)."""
        full, window = self.full.ledger_counts(), self.windowed.ledger_counts()
        with self._lock:
            released = self._released
        return dict(full, full_blocks_held=full["seq_owned"],
                    window_blocks_held=window["seq_owned"], window_free=window["free"],
                    window_blocks_released=released)

    def stats(self) -> dict:
        """The three counts of ``ledger_counts`` that ``stats()["kv_pool"]``
        shows."""
        counts = self.ledger_counts()
        return {k: counts[k] for k in (
            "full_blocks_held", "window_blocks_held", "window_blocks_released")}

    def utilization(self) -> float:
        return self.full.utilization()

    def can_allocate(self, n_tokens: int, shared: int = 0) -> bool:
        return self.full.can_allocate(n_tokens, shared)

    def allocate(self, seq_id: str, n_tokens: int, shared: Sequence[int] = ()) -> list[int]:
        """Claim the full layers' blocks for ``n_tokens`` and the window
        layers' FIRST block (the others follow step by step: ``slide``), or
        neither."""
        if shared:
            raise ValueError("a sequence with window layers shares no blocks")
        # the ledger entry is the caller's, under ``seq_id``, exactly as
        # KVBlockPool.allocate's own: this only forwards it
        blocks = self.full.allocate(seq_id, n_tokens)  # raylint: disable=RL015
        try:
            self.windowed.allocate(seq_id, 1)  # raylint: disable=RL015
        except Exception:
            self.full.free(seq_id)
            raise
        with self._lock:
            self._first[seq_id] = 0
        return blocks

    def slide(self, seq_id: str, start: int, n: int) -> None:
        """Make the window layers ready for a step of ``seq_id`` that writes
        positions ``start .. start + n``, its first query at ``start``: hand
        back every block wholly behind that query's window, then claim up to
        the last position written."""
        bs, pool = self.cfg.block_size, self.windowed
        keep_from = max(start - (self.cfg.window - 1), 0) // bs
        end = min(start + n, self.cfg.max_seq_len)
        with self._lock:
            # steps follow one another (a chunk or a decode starts where the
            # last one ended), so ``keep_from`` lies inside what is held
            gone = pool.release_head(seq_id, keep_from - self._first[seq_id])
            self._first[seq_id] = first = self._first[seq_id] + gone
            self._released += gone
            if not pool.grow_to(seq_id, end - first * bs):
                raise MemoryError(
                    f"window sub-pool exhausted ({pool.num_free_blocks} free): it holds "
                    f"{self.cfg.window_blocks_per_seq} blocks for each of {self.cfg.slots} "
                    "slots, which no sequence in a slot can pass")

    def grow_to(self, seq_id: str, n_tokens: int) -> bool:
        """Room for a decode that writes position ``n_tokens - 1``: the full
        layers' block where one is due (False, nothing changed, when their
        pool is dry), then the window layers' slide to that query."""
        if not self.full.grow_to(seq_id, n_tokens):
            return False
        self.slide(seq_id, min(n_tokens, self.cfg.max_seq_len) - 1, 1)
        return True

    def free(self, seq_id: str) -> int:
        freed = self.full.free(seq_id)
        self.windowed.free(seq_id)
        with self._lock:
            self._first.pop(seq_id, None)
        return freed

    def blocks_of(self, seq_id: str) -> list[int]:
        return self.full.blocks_of(seq_id)

    def _window_blocks(self, seq_id: str) -> list:
        try:
            return self.windowed.blocks_of(seq_id)
        except KeyError:  # freed since the audit read its owners
            return []

    def window_blocks_of(self, seq_id: str) -> tuple:
        """(logical index of the first window block held, the blocks)."""
        with self._lock:
            return self._first[seq_id], self.windowed.blocks_of(seq_id)

    def audit(self) -> dict:
        """Both parts' audits and every owner in both: the full sub-pool's
        keys, ``window`` the window sub-pool's, ``unpaired`` the owners of one
        and not the other (read again once, as ``HybridPool.audit``), and no
        sequence over its most window blocks."""
        for _ in range(2):
            full, win = self.full.audit(), self.windowed.audit()
            unpaired = sorted(set(full["owners"]) ^ set(win["owners"]))
            if not unpaired:
                break
        over = [s for s in win["owners"] if s not in unpaired
                and len(self._window_blocks(s)) > self.cfg.window_blocks_per_seq]
        return dict(full, ok=full["ok"] and win["ok"] and not unpaired and not over,
                    window=win, unpaired=unpaired, over_window=over)

    def table_row(self, seq_id: Optional[str]) -> np.ndarray:
        """(2 * max_blocks_per_seq,) int32: the full layers' table, then
        the window layers' by logical block; ``None`` is all trash."""
        row = np.zeros(self.cfg.max_blocks_per_seq, np.int32)
        if seq_id is not None:
            first, blocks = self.window_blocks_of(seq_id)
            row[first:first + len(blocks)] = blocks
        return np.concatenate([self.full.table_row(seq_id), row])

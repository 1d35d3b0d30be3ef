"""Pooled, slot-indexed KV-cache manager.

One preallocated pytree holds the decode state for every slot — attention
archs get ``(slots, heads, max_len, head_dim)`` K/V buffers (or MLA latent
buffers) in the serving cache dtype with a per-slot write cursor
(``cache["lengths"]``); RWKV gets per-slot recurrent state.  Slots are
recycled: freeing is O(1) bookkeeping (the cursor reset masks stale
entries; the next occupant overwrites them chunk by chunk).

The pool owns the cache pytree functionally: the engine reads
``pool.cache``, runs the jitted step, and stores the result back with
:meth:`update`.  A slot owns a contiguous ``max_len`` stripe; the paged
(block-granular, prefix-sharing) alternative lives in
``repro.serving.paged`` behind ``EngineConfig.kv_layout``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import ModelApi

_CACHE_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                 "int8": jnp.int8}


class SlotPool:
    """Fixed number of sequence slots over one pooled cache pytree."""

    def __init__(self, api: ModelApi, slots: int, max_len: int,
                 cache_dtype: str = "bfloat16") -> None:
        if not api.supports_slots:
            raise NotImplementedError(
                f"{api.cfg.name}: architecture not servable through the slot "
                "engine yet (ring-buffer / SSM slot state are ROADMAP items)")
        self.slots = slots
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.cache = api.init_slot_cache(slots, max_len,
                                         _CACHE_DTYPES[cache_dtype])
        # attention caches mask stale entries by position, so slot recycling
        # is cursor-reset only; RECURRENT state (rwkv) has no mask — the
        # previous occupant's state must be zeroed on reassignment
        self._recurrent = bool(api.cfg.rwkv)
        # recurrent-state zeroing for one slot, fused: one jitted dispatch
        # updating every state leaf (slot index traced -> compiles once),
        # instead of one .at[:, slot].set(0) dispatch per leaf per admission
        self._zero_slot = jax.jit(
            lambda leaves, slot: jax.tree.map(
                lambda v: v.at[:, slot].set(0), leaves)) \
            if self._recurrent else None
        self._free: list[int] = list(range(slots - 1, -1, -1))  # pop -> slot 0 first
        self._owner: dict[int, int] = {}  # slot -> rid

    # -- allocation ----------------------------------------------------------

    def acquire(self, rid: int) -> int | None:
        """Claim a free slot for request ``rid`` (cursor reset to 0)."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = rid
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(0)
        if self._recurrent:
            # collect the state keys first — never mutate the dict being
            # iterated — then zero every leaf in one fused update
            keys = [k for k in self.cache if k != "lengths"]  # (L, slots, ...)
            zeroed = self._zero_slot({k: self.cache[k] for k in keys},
                                     jnp.asarray(slot, jnp.int32))
            self.cache.update(zeroed)
        return slot

    def acquire_for(self, req) -> int | None:
        """Request-aware acquire (the scheduler's entry point; the paged
        pool overloads it with prefix matching and block reservation).
        The contiguous layout needs nothing beyond a free slot."""
        return self.acquire(req.rid)

    def release(self, slot: int) -> None:
        del self._owner[slot]
        self._free.append(slot)

    # -- state ---------------------------------------------------------------

    def update(self, new_cache: dict) -> None:
        """Store the cache pytree returned by the jitted step."""
        self.cache = new_cache

    def lengths(self) -> np.ndarray:
        """Host copy of the per-slot write cursors."""
        return np.asarray(self.cache["lengths"])

    def set_lengths(self, new_lengths: np.ndarray) -> None:
        """Overwrite every slot's write cursor (speculative-decode rollback).

        A cursor move is a sound rollback for attention-style caches:
        entries beyond a slot's cursor are never attended (the position
        mask) and are overwritten before they are read (the cache write
        lands before attention), so rejected speculative K/V needs no
        erasing — only the cursor retreats.  Recurrent (RWKV) state has no
        cursor to move, which is why the engine refuses speculation there.
        """
        from repro.models.lm import rollback_slots

        self.cache = rollback_slots(self.cache, new_lengths)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.n_active / self.slots

    def owner(self, slot: int) -> int | None:
        return self._owner.get(slot)

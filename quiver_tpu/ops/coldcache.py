"""Host-side metadata for the HBM cold-row overlay cache.

The budgeted feature tier (``Feature`` with ``cache_count <
node_count``) serves every cold row over the host link, every batch —
even when zipf-skewed traffic re-requests the same rows batch after
batch (what that costs on the chip: not measured).  The overlay
cache is a second device-resident tier *behind* the static degree-
ordered hot prefix: a fixed-capacity ``[C, dim]`` HBM table holding
whichever cold rows the traffic keeps touching.

Division of labor (mirrors the hot/cold split itself):

  * **this module** — pure-numpy slot bookkeeping: node-id -> slot map,
    online access-frequency tracking, second-touch admission, CLOCK or
    min-frequency eviction.  No jax imports; the probe/admit split in
    ``Feature._stage`` stays host-side numpy.
  * **feature.py** — the device side: one jax array per overlay, read
    by the cached three-way merge executables and written by cached
    scatter-update executables (static shapes, no retraces).

Thread-safety: instances are **externally synchronized** — every
caller holds the owning store's staging lock (``Feature._plock``)
across probe+admit so the metadata and the captured device table value
stay consistent (see ``Feature._stage``).

The paged feature store (``ops/paged.py``) reuses this class as its
**page table**: the "rows" become host pages, the slots become OVERLAY
frames, and residency/eviction/invalidation/checkpoint export all come
along unchanged (``admit_threshold=1`` there — a touched HOST page
must fault in to be served at all).

Policy notes:

  * *Second-touch admission* (``admit_threshold=2`` default): a row
    enters the overlay only on its ``admit_threshold``-th miss, so
    one-shot scans cannot flush rows the recurring traffic needs
    (ARC/2Q's ghost-list insight, sized to one counter per cold row).
    Duplicate ids inside one batch each count as a touch — a row
    requested twice in a single gather is recurring by definition.
  * *CLOCK eviction*: one ref bit per slot, set on hit, cleared as the
    hand sweeps; the sweep is batched (vectorized over the whole
    admission batch) rather than per-victim, which preserves CLOCK's
    second-chance semantics at numpy speed.
  * *min-frequency eviction* (``policy="minfreq"``): evict the resident
    slots with the smallest hit counts (argpartition over the per-slot
    frequency array) — stickier than CLOCK for stationary zipf traffic,
    slower to adapt when the hot set drifts.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

__all__ = ["ColdRowCache", "COLD_CACHE_POLICIES"]

COLD_CACHE_POLICIES = ("clock", "minfreq")


class ColdRowCache:
    """Fixed-capacity slot table + frequency tracker over a cold-id space.

    Args:
      capacity: number of overlay slots (rows of the device table).
      n_rows: size of the cold-id space being cached over (ids handed to
        :meth:`probe`/:meth:`admit` must be in ``[0, n_rows)``).
      policy: ``"clock"`` or ``"minfreq"`` eviction.
      admit_threshold: a row is admitted on its N-th observed miss
        (1 = admit on first miss).
    """

    def __init__(self, capacity: int, n_rows: int, policy: str = "clock",
                 admit_threshold: int = 2):
        capacity = int(capacity)
        n_rows = int(n_rows)
        if capacity <= 0:
            raise ValueError(f"overlay capacity must be > 0, got {capacity}")
        if policy not in COLD_CACHE_POLICIES:
            raise ValueError(f"cold-cache policy must be one of "
                             f"{COLD_CACHE_POLICIES}, got {policy!r}")
        if admit_threshold < 1:
            raise ValueError("admit_threshold must be >= 1")
        self.capacity = capacity
        self.n_rows = n_rows
        self.policy = policy
        self.admit_threshold = int(admit_threshold)
        self.slot_of = np.full(n_rows, -1, dtype=np.int32)
        self.node_of = np.full(capacity, -1, dtype=np.int64)
        self.freq = np.zeros(capacity, dtype=np.int64)   # per-slot hits
        self.ref = np.zeros(capacity, dtype=np.uint8)    # CLOCK ref bits
        self.touches = np.zeros(n_rows, dtype=np.int32)  # misses per row
        self.hand = 0
        self.next_free = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # brownout switch (QoS degradation ladder L2): while True,
        # admit() stops taking new rows — probes and hits still serve,
        # but no slot churn / device row writes happen under overload
        self.admission_paused = False

    # ------------------------------------------------------------------
    def probe(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Hit/miss split for one batch of cold-space ids.

        Returns ``(hit_mask, slots)`` aligned with ``ids``; ``slots`` is
        only meaningful where ``hit_mask``.  Side effects: bumps per-slot
        frequency + CLOCK ref bits for hits, and per-row touch counts
        for misses (the admission evidence :meth:`admit` reads).
        """
        ids = np.asarray(ids, dtype=np.int64)
        slots = self.slot_of[ids]
        hit = slots >= 0
        hs = slots[hit]
        if hs.size:
            np.add.at(self.freq, hs, 1)
            self.ref[hs] = 1
            self.hits += int(hs.size)
        miss_ids = ids[~hit]
        if miss_ids.size:
            np.add.at(self.touches, miss_ids, 1)
            self.misses += int(miss_ids.size)
        return hit, slots

    # ------------------------------------------------------------------
    def admit(self, ids: np.ndarray,
              protect_slots=None) -> Tuple[np.ndarray, int]:
        """Assign slots to the missed rows that earned admission.

        ``ids`` are the missed cold-space ids of one batch (touch counts
        already bumped by :meth:`probe`).  Returns ``(slots, n_evicted)``
        where ``slots`` is aligned with ``ids`` (-1 = not admitted;
        duplicates of one id share its slot).  At most ``capacity`` rows
        admit per call; the overflow stays host-served this batch.

        ``protect_slots`` pins already-resident slots against this
        call's eviction sweep — the paged store passes the batch's
        OVERLAY-hit pages here, since the gather about to run reads
        them (evicting a same-batch hit would serve a retargeted page).
        The count of candidates is clipped so protection can never make
        the sweep need more victims than the unprotected slots can
        supply.
        """
        ids = np.asarray(ids, dtype=np.int64)
        out = np.full(len(ids), -1, dtype=np.int32)
        if not len(ids) or self.admission_paused:
            return out, 0
        cand = np.unique(ids[self.touches[ids] >= self.admit_threshold])
        n_prot = (len(np.unique(protect_slots))
                  if protect_slots is not None and len(protect_slots)
                  else 0)
        cand = cand[: self.capacity - n_prot]
        k = len(cand)
        if k == 0:
            return out, 0
        slots = np.empty(k, dtype=np.int32)
        n_new = min(self.capacity - self.next_free, k)
        if n_new:
            slots[:n_new] = np.arange(self.next_free, self.next_free + n_new,
                                      dtype=np.int32)
            self.next_free += n_new
        n_evicted = 0
        if k > n_new:
            # protect the slots just taken from the free list: their
            # ref/freq are still zero here, so an unprotected sweep
            # would hand them out twice (two ids sharing one slot)
            prot = slots[:n_new]
            if n_prot:
                prot = np.concatenate(
                    [prot, np.asarray(protect_slots, dtype=np.int32)])
            victims = self._evict(k - n_new, protect=prot)
            slots[n_new:] = victims
            old = self.node_of[victims]
            live = old >= 0
            self.slot_of[old[live]] = -1
            n_evicted = int(live.sum())
            self.evictions += n_evicted
        self.node_of[slots] = cand
        self.slot_of[cand] = slots
        self.freq[slots] = 1
        # insert with ref=0: the admission evidence (touches) is spent;
        # the ref bit tracks POST-admission reuse, so the sweep can tell
        # still-recurring rows from one-burst admits
        self.ref[slots] = 0
        self.touches[cand] = 0
        out = self.slot_of[ids]  # admitted ids resolve, the rest stay -1
        return out, n_evicted

    def _evict(self, need: int, protect=None) -> np.ndarray:
        prot = np.zeros(self.capacity, dtype=bool)
        if protect is not None and len(protect):
            prot[protect] = True
        if self.policy == "minfreq":
            # smallest-hit-count resident slots; O(C) per admission batch
            f = self.freq.copy()
            f[prot] = np.iinfo(f.dtype).max
            idx = np.argpartition(f, need - 1)[:need]
            return idx.astype(np.int32)
        # batched CLOCK: scan from the hand; slots with ref=0 are victims,
        # every slot passed on the way loses its ref bit (second chance)
        cap = self.capacity
        order = np.concatenate(
            [np.arange(self.hand, cap), np.arange(0, self.hand)]
        ).astype(np.int32)
        order = order[~prot[order]]
        zero_pos = np.nonzero(self.ref[order] == 0)[0]
        if len(zero_pos) >= need:
            last = int(zero_pos[need - 1])
            self.ref[order[: last + 1]] = 0
            self.hand = int(order[last] + 1) % cap
            return order[zero_pos[:need]]
        # a full sweep found < need zeros: every scanned bit is cleared,
        # the remainder comes from the (now all-zero) second sweep in order
        victims = order[zero_pos]
        taken = np.zeros(cap, dtype=bool)
        taken[victims] = True
        rest = order[~taken[order]][: need - len(victims)]
        self.ref[order] = 0
        out = np.concatenate([victims, rest]).astype(np.int32)
        self.hand = int(out[-1] + 1) % cap
        return out

    # ------------------------------------------------------------------
    def invalidate_rows(self, rows: np.ndarray) -> int:
        """Drop the given cold-space rows from the overlay.

        Called when the underlying feature rows mutate (stream edge/row
        updates): a resident slot would otherwise keep serving the stale
        value forever.  The freed slots keep ``ref=0``/``freq=0`` so the
        next CLOCK sweep hands them out first; touch counts are also
        reset so a mutated row must re-earn admission (second touch)
        rather than re-admitting off pre-mutation evidence.

        Returns the number of resident rows actually dropped.
        """
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size == 0:
            return 0
        rows = rows[(rows >= 0) & (rows < self.n_rows)]
        if rows.size == 0:
            return 0
        slots = self.slot_of[rows]
        live = slots >= 0
        freed = slots[live]
        if freed.size:
            self.node_of[freed] = -1
            self.freq[freed] = 0
            self.ref[freed] = 0
            self.slot_of[rows[live]] = -1
        self.touches[rows] = 0
        return int(freed.size)

    # ------------------------------------------------------------------
    def export_state(self) -> dict:
        """Snapshot-able residency/frequency state (numpy copies +
        scalars; the recovery checkpoint pins the array dtypes on
        disk).  Caller holds the owning store's staging lock, same as
        every other entry point."""
        return {
            "capacity": self.capacity, "n_rows": self.n_rows,
            "policy": self.policy, "admit_threshold": self.admit_threshold,
            "hand": self.hand, "next_free": self.next_free,
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions,
            "slot_of": self.slot_of.copy(), "node_of": self.node_of.copy(),
            "freq": self.freq.copy(), "ref": self.ref.copy(),
            "touches": self.touches.copy(),
        }

    def restore_state(self, state: dict) -> None:
        """Adopt a previously exported state.  The geometry (capacity,
        cold-row space) must match this instance — a warm restart with
        a re-sized overlay starts cold instead (the caller treats the
        ``ValueError`` as "no restore", not as a boot failure)."""
        if int(state["capacity"]) != self.capacity:
            raise ValueError(
                f"overlay capacity changed: snapshot has "
                f"{state['capacity']}, this cache has {self.capacity}")
        if int(state["n_rows"]) != self.n_rows:
            raise ValueError(
                f"cold-row space changed: snapshot has {state['n_rows']} "
                f"rows, this cache has {self.n_rows}")
        self.slot_of = np.array(state["slot_of"], dtype=np.int32)
        self.node_of = np.array(state["node_of"], dtype=np.int64)
        self.freq = np.array(state["freq"], dtype=np.int64)
        self.ref = np.array(state["ref"], dtype=np.uint8)
        self.touches = np.array(state["touches"], dtype=np.int32)
        self.hand = int(state["hand"])
        self.next_free = int(state["next_free"])
        self.hits = int(state["hits"])
        self.misses = int(state["misses"])
        self.evictions = int(state["evictions"])

    # ------------------------------------------------------------------
    @property
    def resident(self) -> int:
        return int((self.node_of >= 0).sum())

    def resident_bytes(self, row_bytes: int) -> int:
        """Device bytes the resident entries pin, given the bytes one
        cached unit occupies (a feature row here; a whole page when the
        paged store uses this class as its page table)."""
        return self.resident * int(row_bytes)

    def stats(self) -> dict:
        total = self.hits + self.misses
        return dict(
            capacity=self.capacity, resident=self.resident,
            hits=self.hits, misses=self.misses, evictions=self.evictions,
            hit_rate=(self.hits / total) if total else 0.0,
            policy=self.policy, admit_threshold=self.admit_threshold,
        )

    def __repr__(self):
        return (f"ColdRowCache(capacity={self.capacity}, "
                f"resident={self.resident}, policy={self.policy!r}, "
                f"hit_rate={self.stats()['hit_rate']:.3f})")

"""Mesh placement layer (DESIGN.md §13.1).

A `MeshContext` wraps the process's JAX devices (CPU emulation via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` gives N of them) and
owns the *placement* of catalog partitions onto them: round-robin over the
alive device slots, same convention as the DESIGN.md §5 ``('data',)`` axis.
Placement is physical-layer state only — it never appears in a logical
plan, so explain() output and plan fingerprints are byte-identical with
sharding on or off.

The context also keeps the lanes of placed partitions on the devices
between dispatches (`ResidentLanes`): a mesh program reads a column it has
read before from device memory, and ships only what changed per query.
An entry belongs to one placement generation and to the host arrays it
was built from, so a device loss or a partition rebuilt from lineage
misses and refills from the host.

Device loss is modeled the way worker loss is in the runtime scheduler:
``kill_device(slot)`` marks the slot dead and bumps the placement
*generation*.  A dispatch that observes a generation change (or catches
`DeviceLost` from a chaos hook) rebuilds the placement over the survivors
and recomputes — results are identical because every mesh program computes
pure partial states from host-resident partitions (the lineage the
single-host path already has).
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# share of the mesh devices' memory the resident lanes may hold
RESIDENT_SHARE = 0.25


class DeviceLost(RuntimeError):
    """A mesh device died mid-dispatch (raised by chaos hooks; real device
    loss would surface as an XLA runtime error wrapped into this)."""

    def __init__(self, slot: int):
        super().__init__(f"mesh device slot {slot} lost")
        self.slot = slot


@dataclass(frozen=True)
class MeshPlacement:
    """Partition -> device-slot assignment for ONE dispatch: round-robin of
    `num_parts` partitions over the alive slots at `generation`."""
    generation: int
    alive_slots: Tuple[int, ...]
    device_of: Tuple[int, ...]          # partition ordinal -> alive-slot index
    parts_per_device: int               # padded per-device partition count

    @property
    def n_devices(self) -> int:
        return len(self.alive_slots)


class ResidentLanes:
    """Device copies of placed partitions' lanes, kept between dispatches.

    An entry is keyed by a tag (which lane of which column) and by the
    identity of the host arrays it was made from, held by weak reference:
    it hits only while those very arrays live and the placement generation
    it was built at holds.  Entries stay under `budget` device bytes,
    least recently used first out; one larger than the budget is not kept.
    """

    def __init__(self, budget: Optional[int]):
        self.budget = budget
        self._lock = threading.RLock()
        # key -> (generation, weak refs to the sources, value, device bytes)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.pinned = 0

    @staticmethod
    def _key(tag: str, sources: Sequence[Any]) -> tuple:
        return (tag,) + tuple(id(s) for s in sources)

    def get(self, tag: str, sources: Sequence[Any], generation: int):
        """The value kept for `sources` at `generation`, or None."""
        key = self._key(tag, sources)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            if entry[0] != generation or any(
                    r() is not s for r, s in zip(entry[1], sources)):
                self._drop(key)
                return None
            self._entries.move_to_end(key)
            return entry[2]

    def keep(self, tag: str, sources: Sequence[Any], generation: int,
             value, nbytes: int) -> int:
        """Keep `value` (holding `nbytes` device bytes) for `sources`: the
        bytes pinned, or 0 where the budget cannot hold it.  The entry goes
        when any of its sources does."""
        key = self._key(tag, sources)
        gone = lambda _r, k=key: self._discard(k)  # noqa: E731
        refs = tuple(weakref.ref(s, gone) for s in sources)
        with self._lock:
            self._discard(key)
            if self.budget is not None:
                if nbytes > self.budget:
                    return 0
                while self.pinned + nbytes > self.budget:
                    self._drop(next(iter(self._entries)))
            self._entries[key] = (generation, refs, value, nbytes)
            self.pinned += nbytes
        return nbytes

    def _drop(self, key: tuple) -> None:
        self.pinned -= self._entries.pop(key)[3]

    def _discard(self, key: tuple) -> None:
        with self._lock:
            if key in self._entries:
                self._drop(key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.pinned = 0

    def __len__(self) -> int:
        return len(self._entries)


def resident_budget(devices) -> Optional[int]:
    """RESIDENT_SHARE of the devices' summed `bytes_limit`, or None (no
    cap) where a device reports no limit."""
    total = 0
    for d in devices:
        limit = (d.memory_stats() or {}).get("bytes_limit")
        if not limit:
            return None
        total += limit
    return int(total * RESIDENT_SHARE)


class MeshContext:
    """Device pool + placement authority for mesh-sharded execution.

    Thread-safe: executors on server worker threads share one context.
    The jitted shard_map programs are cached per (generation, shape) key by
    `cluster.shard_exec`, keyed off `mesh()` which is itself cached per
    generation.
    """

    def __init__(self, max_devices: Optional[int] = None,
                 max_retries: int = 3, policy=None):
        import jax
        devs = list(jax.devices())
        if max_devices is not None:
            devs = devs[:max_devices]
        self.devices = devs
        self.alive: List[bool] = [True] * len(devs)
        self.generation = 0
        # the ResiliencePolicy owns the dispatch retry budget when given
        self.max_retries = (policy.mesh_max_retries if policy is not None
                            else max_retries)
        self.chaos = None   # core.faults.ChaosEngine, when installed
        self.lock = threading.RLock()
        # chaos hook: called at every dispatch with (ctx, dispatch_ordinal);
        # tests install a killer that calls kill_device / raises DeviceLost
        self.on_dispatch: Optional[Callable[["MeshContext", int], None]] = None
        self.dispatches = 0
        self.retries = 0                # dispatches re-run after device loss
        self._mesh_cache: Dict[int, object] = {}    # generation -> Mesh
        self.resident = ResidentLanes(resident_budget(devs))

    # -- device liveness ------------------------------------------------------

    def alive_slots(self) -> List[int]:
        with self.lock:
            return [i for i, a in enumerate(self.alive) if a]

    @property
    def n_alive(self) -> int:
        return len(self.alive_slots())

    def kill_device(self, slot: int) -> None:
        """Chaos: mark a device slot dead.  Every placement built at an
        older generation is stale; in-flight dispatches recompute over the
        survivors."""
        with self.lock:
            if not self.alive[slot]:
                return
            if sum(self.alive) == 1:
                raise RuntimeError("cannot kill the last mesh device")
            self.alive[slot] = False
            self.generation += 1
        self.resident.clear()

    def revive_all(self) -> None:
        with self.lock:
            if all(self.alive):
                return
            self.alive = [True] * len(self.devices)
            self.generation += 1
        self.resident.clear()

    # -- placement ------------------------------------------------------------

    def mesh(self):
        """1-D ('data',) mesh over the alive devices, cached per
        generation (shard_map program caches key off this object)."""
        import jax
        with self.lock:
            gen = self.generation
            m = self._mesh_cache.get(gen)
            if m is None:
                devs = [self.devices[i] for i in self.alive_slots()]
                m = jax.make_mesh((len(devs),), ("data",), devices=devs,
                                  axis_types=(jax.sharding.AxisType.Auto,))
                self._mesh_cache = {gen: m}     # old generations are stale
            return m, gen

    def place(self, num_parts: int) -> MeshPlacement:
        """Round-robin `num_parts` catalog partitions over the alive
        slots.  `parts_per_device` is the padded per-device count (the
        shard_map leading axis is `n_devices * parts_per_device`)."""
        with self.lock:
            slots = tuple(self.alive_slots())
            n = len(slots)
            device_of = tuple(i % n for i in range(num_parts))
            per = max(1, -(-num_parts // n)) if num_parts else 1
            return MeshPlacement(self.generation, slots, device_of, per)

    # -- dispatch bookkeeping -------------------------------------------------

    def fire_dispatch(self) -> int:
        """Invoke the chaos hook (if any) and count the dispatch.  Returns
        the generation observed at dispatch start, so callers can detect a
        placement made stale *during* the dispatch."""
        with self.lock:
            ordinal = self.dispatches
            self.dispatches += 1
            gen = self.generation
        hook = self.on_dispatch
        if hook is not None:
            hook(self, ordinal)
        # chaos seam "mesh.dispatch": kill an alive device slot and raise
        # DeviceLost — the dispatch retry loop re-places over the survivors
        # and recomputes.  Only armed while >1 slot survives (killing the
        # last device would be unrecoverable, not chaos).
        chaos = self.chaos
        if chaos is not None and self.n_alive > 1:
            trip = chaos.fire("mesh.dispatch")
            if trip is not None:
                slots = self.alive_slots()
                victim = slots[trip.ordinal % len(slots)]
                try:
                    self.kill_device(victim)
                except RuntimeError:
                    pass        # raced another killer down to one slot
                raise DeviceLost(victim)
        return gen

    def stats(self) -> Dict[str, int]:
        with self.lock:
            return {"devices": len(self.devices), "alive": sum(self.alive),
                    "generation": self.generation,
                    "dispatches": self.dispatches, "retries": self.retries}

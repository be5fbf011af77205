"""SharedMemoryConnector — slab-arena zero-copy intra-node channel (§4.1.3).

Plays the role of the paper's Margo/UCX RDMA-backed distributed memory for
node-local producers/consumers.  Objects live in a small number of large
pre-created shared-memory **arenas** (see :mod:`repro.core.arena`): ``put``
is one slab allocation + one memcpy per frame segment + one atomic
commit-byte store; ``get`` is a cached arena attach + a slot-entry read +
a zero-copy ``memoryview`` slice the consumer deserializes straight out of
shared memory.  No per-object segments, no filesystem sidecars, no
syscalls on the steady-state hot path.

Key layout: ``("shm", registry_dir, object_id)`` where ``object_id`` is
``"{arena}.{slot}.{gen}"`` — the slot header in the arena IS the object
directory, and the generation makes keys of recycled slots read as
missing instead of aliasing new data.  Reserved keys (futures) are
``"r{uuid}"``: ``put_to`` embeds the uuid in the slot entry and consumers
resolve it by scanning the arenas' slot tables (the rare pre-data path;
the hot path never scans).

Mapped-view lifetime: views returned by ``get`` stay *valid* until the
consumer's connector closes (and survive even that while exported), but
their *contents* are only stable until the object is evicted — after
which the owner may recycle the chunk.  Use refcounts/leases to pin
objects consumers are still reading.

Hardware adaptation note: no RDMA NIC is assumed; POSIX shm is the intra-node analog of memory-to-memory
transfer.  Cross-node traffic falls to SocketConnector/KVServerConnector,
as the paper's ZMQ fallback does.
"""
from __future__ import annotations

import atexit
import uuid
from typing import Any

from repro.core.arena import (DEFAULT_ARENA_SIZE, DEFAULT_NSLOTS, ArenaPool,
                              NO_ID)
from repro.core.connector import BaseConnector, Key
from repro.core.serialize import as_segments, frame_nbytes

_RESERVED = "r"     # reserved-key object_id prefix (no "." — arena ids have 3)


class SharedMemoryConnector(BaseConnector):
    """Arena-backed shm store.

    ``registry_dir`` is a small shared directory (tmpfs is fine) holding one
    marker file per *arena* (written once at arena creation) — per-object
    traffic never touches the filesystem.  ``arena_size``/``nslots`` size
    the slabs this process creates as a producer; consumers attach whatever
    the registry advertises regardless of their own settings.
    """

    # gets return views of arena memory the owner may recycle post-evict:
    # lifecycle-bound Store resolves materialize before dropping their ref
    borrows_get = True

    def __init__(self, registry_dir: str, clear: bool = False,
                 arena_size: int = DEFAULT_ARENA_SIZE,
                 nslots: int = DEFAULT_NSLOTS) -> None:
        self.registry_dir = str(registry_dir)
        self.arena_size = int(arena_size)
        self.nslots = int(nslots)
        self._pool = ArenaPool(self.registry_dir, self.arena_size,
                               self.nslots)
        # orphan sweep: tmp sidecars + dead markers always; with clear=True
        # also dead-owner arenas and legacy per-object segments
        self._pool.sweep(clear=clear)
        # reserved-id -> located object_id (the scan runs once per id)
        self._resolved: dict[str, str] = {}
        atexit.register(self.close)

    # -- id plumbing ---------------------------------------------------------
    @staticmethod
    def _encode(arena: str, slot: int, gen: int) -> str:
        return f"{arena}.{slot}.{gen}"

    def _locate(self, object_id: str) -> tuple[str, int, int] | None:
        """Resolve an object_id to (arena, slot, gen); reserved ids go
        through the slot-table scan (cached after the first hit)."""
        if object_id.startswith(_RESERVED):
            hit = self._resolved.get(object_id)
            if hit is None:
                found = self._pool.find_id(
                    bytes.fromhex(object_id[len(_RESERVED):]))
                if found is None:
                    return None
                hit = self._encode(*found)
                self._resolved[object_id] = hit
            object_id = hit
        try:
            arena, slot, gen = object_id.rsplit(".", 2)
            return arena, int(slot), int(gen)
        except ValueError:
            return None

    # -- Connector ops -------------------------------------------------------
    def put(self, blob) -> Key:
        loc = self._pool.put(as_segments(blob), frame_nbytes(blob))
        return ("shm", self.registry_dir, self._encode(*loc))

    # -- futures: pre-data keys (the slot's commit byte is the publication
    # point, so waiters never observe a half-written payload) ---------------
    def reserve(self) -> Key:
        return ("shm", self.registry_dir, _RESERVED + uuid.uuid4().hex)

    def put_to(self, key: Key, blob) -> None:
        object_id = key[2]
        idbytes = (bytes.fromhex(object_id[len(_RESERVED):])
                   if object_id.startswith(_RESERVED) else NO_ID)
        loc = self._pool.put(as_segments(blob), frame_nbytes(blob), idbytes)
        if idbytes != NO_ID:
            self._resolved[object_id] = self._encode(*loc)
        self.announce(key)

    def get(self, key: Key):
        loc = self._locate(key[2])
        if loc is None:
            return None
        arena = self._pool.attach(loc[0])
        if arena is None:
            return None
        return arena.read(loc[1], loc[2])

    # -- block-granular reservation (KV-cache paging) ------------------------
    # A ``put`` whose payload the caller writes in place: reserve hands out
    # the slot's writable view, the producer fills it (e.g. via
    # ``np.frombuffer``), commit_block flips the publication byte.  Zero
    # staging copies between the compute and the shared mapping.
    supports_blocks = True

    def reserve_block(self, nbytes: int) -> tuple[Key, memoryview]:
        loc, view = self._pool.reserve_direct(nbytes)
        return ("shm", self.registry_dir, self._encode(*loc)), view

    def commit_block(self, key: Key) -> None:
        loc = self._locate(key[2])
        if loc is None:
            raise KeyError(f"not an arena key: {key}")
        self._pool.commit_direct(loc[0], loc[1])

    def exists(self, key: Key) -> bool:
        loc = self._locate(key[2])
        if loc is None:
            return False
        arena = self._pool.attach(loc[0])
        return arena is not None and arena.committed(loc[1], loc[2])

    def evict(self, key: Key) -> None:
        loc = self._locate(key[2])
        if loc is None:
            return
        self._pool.free(*loc)
        if key[2].startswith(_RESERVED):
            self._resolved.pop(key[2], None)

    def _lifetime_scope(self):
        return self.registry_dir   # reconnections share the count table

    def config(self) -> dict[str, Any]:
        return {"registry_dir": self.registry_dir,
                "arena_size": self.arena_size, "nslots": self.nslots}

    def stats(self) -> dict[str, Any]:
        return self._pool.stats()

    def enable_sanitizer(self) -> None:
        """Poison-on-free + quarantine + exported-view tracking for every
        arena this connector maps (``Store(..., sanitize=True)`` calls
        this; ``REPRO_SANITIZE=1`` enables it at pool construction)."""
        self._pool.enable_sanitizer()

    def close(self) -> None:
        """Unlink arenas created by this process, detach attached ones.
        Mappings with exported zero-copy views stay alive for the GC."""
        self._pool.close()
        self._resolved.clear()
        self._drop_lifetime_state()

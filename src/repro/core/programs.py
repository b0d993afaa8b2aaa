"""Process-wide caches of each engine's jitted programs.

JAX keys its in-memory trace and executable caches on the function object,
so an engine that wraps a fresh closure in ``jax.jit`` for every search
pays a full trace and lowering (and a persistent-cache load) at every
search start, even when the program it builds is the one the previous
search built.  A :class:`ProgramCache` keeps the ``jax.jit`` object per
*program key* -- what the traced closure reads: the ``EnvConfig``, the
engine's static settings and a digest of the ``EnvArrays`` contents it
closes over -- so a later search with the same key dispatches through
JAX's in-memory executable cache with no trace at all.  Each engine builds
its key beside the closure it describes, from exactly the inputs the
closure is built from (``reinforce._search_program``,
``ga.engine_programs``).

The cached closure is traced exactly as a fresh one would be (the env
arrays stay closed-over constants), so answers are byte-identical with or
without a hit; the search's seed and length reach the program only through
its state and the static chunk length, and stay out of the key.  Each
engine has one bounded LRU (:data:`MAX_PROGRAMS` entries) shared by every
thread of the process.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict, Hashable

import numpy as np

from repro.obs import instrument as obs_instrument

MAX_PROGRAMS = 32   # jitted programs kept per engine


def digest(*arrays) -> bytes:
    """Content digest of arrays (dtype, shape and bytes of each)."""
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.digest()


class ProgramCache:
    """A thread-safe LRU of one engine's jitted programs."""

    def __init__(self, engine: str):
        self.engine = engine
        self._lock = threading.Lock()
        self._programs: "OrderedDict[Hashable, Callable]" = OrderedDict()

    def get(self, key: Hashable, build: Callable[[], Callable]) -> Callable:
        """The program cached under ``key``, else ``build()`` (which only
        wraps a closure in ``jax.jit``; tracing happens at its first call)
        cached under it.  Ticks ``repro_engine_programs`` once."""
        with self._lock:
            fn = self._programs.get(key)
            if fn is not None:
                self._programs.move_to_end(key)
                result = "reused"
            else:
                fn = self._programs[key] = build()
                if len(self._programs) > MAX_PROGRAMS:
                    self._programs.popitem(last=False)
                result = "built"
        obs_instrument.ENGINE_PROGRAMS.inc(engine=self.engine, result=result)
        return fn

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

    def __len__(self) -> int:
        return len(self._programs)


_caches: Dict[str, ProgramCache] = {}
_caches_lock = threading.Lock()


def cache(engine: str) -> ProgramCache:
    """The process-wide :class:`ProgramCache` of ``engine``."""
    with _caches_lock:
        c = _caches.get(engine)
        if c is None:
            c = _caches[engine] = ProgramCache(engine)
        return c


def clear() -> None:
    """Drop every engine's cached programs (the next search of each key
    traces afresh, as in a new process)."""
    with _caches_lock:
        for c in _caches.values():
            c.clear()

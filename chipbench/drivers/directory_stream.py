"""Directory stream driver: the stream driver (``drivers/stream.py``) on a
flat cache whose policy keeps a directory beyond its residents, ARC's ghost
lists B1 and B2 beside T1 and T2 (at most 2c entries).

Set-up pushes chunks until the cache is full and the directory holds 2c
entries, or ``WARM_MAX_CHUNKS``, so that the window sees the policy's
steady state, ghosts included.

The window also reports the engine's lane counters over its own chunks:
``lanes``, the lanes stepped, ``lanes_valid``, those holding a real object,
and ``ghost_lanes``, those holding a ghost at their chunk's start
(``StreamStats``); a program without one leaves it ``None``. They are read
at the end of set-up and in ``release()``, after the window has closed, so
that the window (and a profile of it) holds no host sync the loop does not.

Correctness: as the stream driver, every chunk replayed through the plain
reference of the configuration's kind."""
from __future__ import annotations

import numpy as np

from chipbench import cells

stream = cells.load_module("drivers", "stream")

#: the ``StreamStats`` counters the window hands on
COUNTERS = ("lanes", "lanes_valid", "ghost_lanes")


class Driver(stream.Driver):
    def _directory(self) -> int:
        """Entries on any of the four lists (``lst != 0``)."""
        return int((np.asarray(self.stream.states()[0]["lst"]) != 0).sum())

    def _edges_full(self) -> bool:
        # set-up's stop: the cache full and the directory at its 2c bound
        return super()._edges_full() and self._directory() >= 2 * self.capacity[0]

    def setup(self) -> dict:
        warm = super().setup()
        # also compiles the few small programs stats() runs, before the window
        self.counters_from = self.stream.stats()
        return {**warm, "directory": self._directory()}

    def window(self, seconds: float) -> dict:
        self.last_window = super().window(seconds)
        return self.last_window

    def release(self) -> dict:
        st = self.stream.stats()
        for k in COUNTERS:
            a, b = getattr(st, k, None), getattr(self.counters_from, k, None)
            self.last_window[k] = None if a is None or b is None else a - b
        return super().release()

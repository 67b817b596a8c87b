"""Plain perfect LFU (arXiv:2503.02504 §2.2): every object keeps its request
count, cached or not. A miss inserts the object with its count plus one,
first evicting the cached object of least count (lowest id on ties), whose
count is parked until it returns."""
import heapq


class Policy:
    def __init__(self, capacity: int, **_):
        self.capacity = int(capacity)
        self._freq = {}  # cached object -> count
        self._parked = {}  # evicted object -> count
        self._heap = []  # (count, id) snapshots; stale ones are skipped

    def request(self, x: int) -> bool:
        freq = self._freq
        f = freq.get(x)
        if f is not None:
            freq[x] = f + 1
            heapq.heappush(self._heap, (f + 1, x))
            return True
        f_new = self._parked.pop(x, 0) + 1
        if len(freq) >= self.capacity:
            heap = self._heap
            while True:
                f_v, v = heapq.heappop(heap)
                if freq.get(v) == f_v:
                    break
            del freq[v]
            self._parked[v] = f_v
        freq[x] = f_new
        heapq.heappush(self._heap, (f_new, x))
        return False

    def contains(self, x: int) -> bool:
        return x in self._freq

    def __len__(self) -> int:
        return len(self._freq)

"""Plain LRU: a hit moves the object to the recent end; a miss inserts it,
first evicting the least recently used object when the cache is full."""
from collections import OrderedDict


class Policy:
    def __init__(self, capacity: int, **_):
        self.capacity = int(capacity)
        self._od = OrderedDict()

    def request(self, x: int) -> bool:
        od = self._od
        if x in od:
            od.move_to_end(x)
            return True
        if len(od) >= self.capacity:
            od.popitem(last=False)
        od[x] = None
        return False

    def contains(self, x: int) -> bool:
        return x in self._od

    def __len__(self) -> int:
        return len(self._od)

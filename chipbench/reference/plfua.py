"""Plain PLFUA (arXiv:2503.02504 §4): perfect LFU over a hot set fixed in
advance, the ``hot_size`` most popular ranks (ids below it). A request for
an object outside the hot set is a miss that leaves no trace."""
from chipbench.reference import plfu


class Policy:
    def __init__(self, capacity: int, hot_size: int, **_):
        self.hot_size = int(hot_size)
        self._plfu = plfu.Policy(capacity)

    def request(self, x: int) -> bool:
        return x < self.hot_size and self._plfu.request(x)

    def contains(self, x: int) -> bool:
        return self._plfu.contains(x)

    def __len__(self) -> int:
        return len(self._plfu)

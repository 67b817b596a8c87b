"""Plain ARC(c) (Megiddo & Modha, "ARC: A Self-Tuning, Low Overhead
Replacement Cache", USENIX FAST 2003, section III and Fig. 4), as the
figure writes it: four LRU lists over the ids, each an ordered dict whose
first key is its LRU end.

* T1, T2: the resident pages seen once, and at least twice, recently;
* B1, B2: ghosts, the ids alone, of pages evicted from T1 and from T2.

The target ``p`` of T1's size starts at 0 and moves on a ghost hit by the
integer part of the figure's delta. The cache holds |T1| + |T2| <= c pages
and the directory, all four lists, at most 2c ids."""
from collections import OrderedDict


class Policy:
    def __init__(self, capacity: int, **_):
        self.c = int(capacity)
        self.p = 0
        self.t1, self.t2 = OrderedDict(), OrderedDict()
        self.b1, self.b2 = OrderedDict(), OrderedDict()

    def _replace(self, in_b2: bool) -> None:
        """REPLACE(x, p): T1's LRU page to B1's MRU end when T1 is not empty
        and exceeds p (or equals it when x is in B2), else T2's LRU page to
        B2's MRU end."""
        t1 = self.t1
        if t1 and (len(t1) > self.p or (in_b2 and len(t1) == self.p)):
            old, _ = t1.popitem(last=False)
            self.b1[old] = None
        else:
            old, _ = self.t2.popitem(last=False)
            self.b2[old] = None

    def request(self, x: int) -> bool:
        t1, t2, b1, b2, c = self.t1, self.t2, self.b1, self.b2, self.c
        # Case I: a hit in T1 or T2 moves x to T2's MRU end
        if x in t1:
            del t1[x]
            t2[x] = None
            return True
        if x in t2:
            t2.move_to_end(x)
            return True
        # Case II: a ghost of B1 grows p, then x is fetched into T2
        if x in b1:
            delta = 1 if len(b1) >= len(b2) else len(b2) // len(b1)
            self.p = min(c, self.p + delta)
            self._replace(False)
            del b1[x]
            t2[x] = None
            return False
        # Case III: a ghost of B2 shrinks p, then x is fetched into T2
        if x in b2:
            delta = 1 if len(b2) >= len(b1) else len(b1) // len(b2)
            self.p = max(0, self.p - delta)
            self._replace(True)
            del b2[x]
            t2[x] = None
            return False
        # Case IV: a page in no list
        if len(t1) + len(b1) == c:
            # A: L1 = T1 ∪ B1 holds exactly c pages
            if len(t1) < c:
                b1.popitem(last=False)
                self._replace(False)
            else:
                t1.popitem(last=False)
        else:
            # B: L1 holds fewer than c pages
            total = len(t1) + len(t2) + len(b1) + len(b2)
            if total >= c:
                if total == 2 * c:
                    b2.popitem(last=False)
                self._replace(False)
        t1[x] = None
        return False

    def contains(self, x: int) -> bool:
        return x in self.t1 or x in self.t2

    def __len__(self) -> int:
        return len(self.t1) + len(self.t2)

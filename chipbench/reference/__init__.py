"""The plain reference, independent of the program: one policy kind a file
(``Policy(capacity, hot_size, n_objects)`` with ``request(id) -> hit``) and
the tier-tree replay in ``fleet.py``."""

"""Drivers, one file each, named by a traffic mix's ``driver``: a class
``Driver(cell, seed, span)`` with ``setup()``, ``window(seconds)``,
``release()`` and ``check()``."""

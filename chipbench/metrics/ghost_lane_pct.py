"""Share of the fast path's compact lanes that hold an ARC ghost (B1 or B2)
at their chunk's start, over the window's chunks: 100 x ``ghost_lanes`` /
``lanes`` as the directory stream driver reads them from ``StreamStats``.
It is the part of each step's width that ARC's ghost metadata takes.
Nothing where the program does not count ghosts."""


def read(run):
    lanes, ghosts = run.window.get("lanes"), run.window.get("ghost_lanes")
    return 100.0 * ghosts / lanes if lanes and ghosts is not None else None

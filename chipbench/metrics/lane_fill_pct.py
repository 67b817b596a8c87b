"""Share of the engine's stepped lanes that served a request, over the
window's chunks: 100 x ``lanes_valid`` / ``lanes`` as the routed stream
driver reads them from ``StreamStats`` (on the level-major engine a lane is
one node's masked step at one position, valid where that node held an
active request). Nothing where the program does not count them."""


def read(run):
    lanes, valid = run.window.get("lanes"), run.window.get("lanes_valid")
    return 100.0 * valid / lanes if lanes else None

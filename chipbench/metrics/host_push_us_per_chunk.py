"""Host microseconds spent inside ``FleetStream.push`` per chunk, mean over
the traced window's chunks (the benchmark's own clock around each call)."""


def read(run):
    push = run.window.get("push_s")
    return sum(push) / len(push) * 1e6 if push else None

"""Share of the traced window in which no program ran on the chip: 100 x
(1 - union of program executions / window). A trace that holds no chip
plane reads 100: the whole window idled."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

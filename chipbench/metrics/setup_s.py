"""Set-up seconds: from the process's start (before JAX is imported) to the
end of the warm-up, compilation or compile-cache loads included."""


def read(run):
    return run.setup_s

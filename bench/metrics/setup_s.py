"""setup_s: from the start of the process to the start of the window:
imports, operands from the seed, the plan check and the warm-up, which
compiles or loads every executable the window uses."""


def read(r):
    if r.trace is not None:
        return None
    return r.setup_s

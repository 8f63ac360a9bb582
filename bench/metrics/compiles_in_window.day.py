"""Programs JAX built inside the window of a day cell
(``CompileWatch.count``; cache loads included)."""


def read(run):
    return run.compiles

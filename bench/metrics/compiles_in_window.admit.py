"""Programs JAX built inside the window of an admission cell
(``CompileWatch.count``; cache loads included)."""


def read(run):
    return run.compiles

"""Backend compilations (compile-cache loads included) inside the
measured window: a shape the warm-up missed."""


def read(run):
    return run.compiles_in_window

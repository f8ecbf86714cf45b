"""Backend compilations and persistent-cache loads inside the window, as
JAX's monitoring events report them. Warm-up aims to leave none. Layer:
runtime."""


def read(run):
    return len(run.compiles)

"""Programs compiled or loaded from the persistent cache while the window
ran, from JAX's monitoring events. Every shape is warmed up in set-up,
so this reads 0. Layer: round program."""


def read(run):
    return run.compiles

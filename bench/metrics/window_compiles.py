"""Programs compiled or loaded from the persistent cache after the window
opened (JAX's backend-compile events); the benchmark warms every shape
first, so this should read 0 (engine / jit)."""


def read(rec):
    return float(rec["window_compiles"])

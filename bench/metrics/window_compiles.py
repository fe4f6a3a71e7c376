"""Executables JAX built (compiled or loaded) inside the window."""


def read(rec):
    return rec["compile"]["window_count"]

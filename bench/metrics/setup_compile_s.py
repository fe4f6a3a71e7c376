"""Seconds JAX spent building executables (compiled or loaded from
the persistent cache) during set-up."""


def read(rec):
    return rec["compile"]["setup_s"]

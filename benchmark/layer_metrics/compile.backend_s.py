"""Seconds the XLA backend spent compiling before the window (jax's
monitoring events): about 0 once every program is in the persistent cache."""


def read(run):
    return run.compiles.until("window_start")["backend_s"]

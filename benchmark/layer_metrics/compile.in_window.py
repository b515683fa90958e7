"""Compile requests inside the window (jax's monitoring events): a shape
the warm-up missed. Anything but 0 also fails the run's `correct`."""


def read(run):
    return run.compiles.between("window_start", "window_end")["requests"]

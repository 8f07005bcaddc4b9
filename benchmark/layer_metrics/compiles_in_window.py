"""Compilations (and persistent-cache loads of a program the process had
not loaded) that jax.monitoring reported between the window's start and
its end. Has to read 0: everything is warmed in set-up."""


def read(r):
    return r.compiles_in_window

"""Executables compiled or loaded from the compile cache during the
window, from ``jax.monitoring``'s backend-compile events. Should read 0:
every shape is warmed up in set-up."""


def read(inp):
    return inp.compiles_in_window

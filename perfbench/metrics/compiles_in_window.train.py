"""Executables JAX built or loaded inside the measured window (its
backend-compile event); set-up's are not counted."""


def read(ctx):
    return ctx.compiles_in_window

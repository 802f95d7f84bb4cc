"""Programs compiled, or loaded from the persistent cache, inside the
window (jax's own monitoring events). Must be 0: set-up warms every shape."""


def read(ctx):
    return ctx["compiles_in_window"]

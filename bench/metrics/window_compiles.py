"""Backend compiles (or persistent-cache loads) inside the window; every
shape is warmed in set-up, so this should read 0."""


def read(ctx):
    return ctx.compiles_in_window

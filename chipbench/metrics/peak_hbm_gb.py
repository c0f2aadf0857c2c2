"""``peak_bytes_in_use`` after the window, on the fullest chip, in GB."""


def read(ctx):
    return ctx.peak_bytes / 1e9

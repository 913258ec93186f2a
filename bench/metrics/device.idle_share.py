"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, mean over the chips."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * red.idle_share

"""Seconds from process start to the window's opening: imports, weights,
engine build with its AOT compiles (or cache loads), warm-up of every
shape, and the mix's lead-in traffic."""


def read(rec):
    return rec.setup_s

"""Set-up seconds: process start to the window's start, compilation,
loading from the compile cache and the warm-up studies included."""


def read(rec):
    return rec.setup_s

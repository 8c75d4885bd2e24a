"""Seconds from process start to the window's start: imports, data and
weights from the seed, compilation or the persistent cache, warm-up."""


def read(m):
    return m.setup_s

"""Channel drops (seeds x rounds) completed by the window's calls over the
window's wall time."""


def read(m):
    return sum(u["work"] for u in m.units) / m.window_s

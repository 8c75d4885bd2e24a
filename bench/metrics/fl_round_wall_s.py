"""Wall time of the window's rounds over their number (wall, not the
simulated NOMA seconds of ``History.round_time``)."""


def read(m):
    return sum(u["wall_s"] for u in m.units) / len(m.units)

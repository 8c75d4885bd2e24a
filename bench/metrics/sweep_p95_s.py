"""95th percentile, over every call of the window, of one call's wall
time."""
import statistics


def read(m):
    walls = [u["wall_s"] for u in m.units]
    if len(walls) == 1:
        return walls[0]
    return statistics.quantiles(walls, n=100, method="inclusive")[94]

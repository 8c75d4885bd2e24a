"""Share of the traced window in which no operation ran on the device,
in percent (1 - busy union / window)."""


def read(m):
    return 100.0 * m.reduction.idle_share

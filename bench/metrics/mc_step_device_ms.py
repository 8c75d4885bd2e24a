"""Device time of the jitted Monte-Carlo round step (``core/engine.py``
``_montecarlo_step``) per execution, in ms."""


def read(m):
    s, n = m.reduction.module_time(r"^jit__montecarlo_step$")
    return 1e3 * s / n if n else None

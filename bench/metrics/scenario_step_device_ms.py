"""Device time of the jitted scenario step (``sim/scenario.py``
``_step_core``) per execution, in ms."""


def read(m):
    s, n = m.reduction.module_time(r"^jit__step_core$")
    return 1e3 * s / n if n else None

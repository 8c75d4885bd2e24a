"""Device time of the clients' jitted SGD step (``fl/client.py``
``make_sgd_batch_step``) per execution, in ms."""


def read(m):
    s, n = m.reduction.module_time(r"^jit_step$")
    return 1e3 * s / n if n else None

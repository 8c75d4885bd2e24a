"""Faults planted under the timed path, for the tests and for reading a
fault's numbers on the chip (``bench/control.py --fault``): each returns
(module, attribute, replacement) for a monkeypatch of the program.

  state_unchanged  the step returns its state unchanged
  half_batch       half of the batch left out, the mean taken over the rest
  answer_altered   an answer altered where it is produced
  selection_altered  (FL) the planner's admitted set altered: its last
                   admitted client swapped for the first one left out
  half_client_batches  (FL) each client trains on the first half of the
                   batches it draws, the round's mean taken over those
"""
from __future__ import annotations


def mc(kind: str):
    """Faults of the Monte-Carlo round step (the batch is the drops)."""
    import repro.core.engine as eng
    real = eng._montecarlo_step

    def step(ages, part, *args, **kw):
        out = list(real(ages, part, *args, **kw))
        if kind == "state_unchanged":
            out[0], out[1] = ages, part
        elif kind == "half_batch":
            h = ages.shape[0] // 2
            out[0] = out[0].at[h:].set(ages[h:])
            out[1] = out[1].at[h:].set(part[h:])
        elif kind == "answer_altered":
            out[2] = out[2] * 1.01
        return tuple(out)

    return eng, "_montecarlo_step", step


def fl(kind: str):
    """Faults of a client's SGD step, or of the aggregate it produces."""
    import jax

    import repro.fl.client as client
    import repro.fl.server as server
    if kind == "selection_altered":
        import numpy as np
        real_select = server.FLServer.select

        def select(self, env):
            sched = real_select(self, env)
            sel = np.array(sched.selected, bool)
            inside, outside = np.flatnonzero(sel), np.flatnonzero(~sel)
            if len(inside) and len(outside):
                sel[inside[-1]], sel[outside[0]] = False, True
            sched.selected = sel
            return sched

        return server.FLServer, "select", select
    if kind == "half_client_batches":
        real_batches = server.client_batches

        def batches(rng, data, batch_size, epochs=1):
            out = list(real_batches(rng, data, batch_size, epochs))
            return out[: len(out) // 2]

        return server, "client_batches", batches
    if kind == "answer_altered":
        real_agg = server.aggregate_deltas

        def agg(deltas, weights, *, impl):
            out = real_agg(deltas, weights, impl=impl)
            return jax.tree.map(lambda x: x * 1.5, out)

        return server, "aggregate_deltas", agg
    real_make = client.make_sgd_batch_step

    def make(cfg, lr, momentum=0.0):
        opt, step = real_make(cfg, lr, momentum)

        def broken(params, opt_state, tokens):
            if kind == "half_batch":
                return step(params, opt_state, tokens[: tokens.shape[0] // 2])
            _, st, loss = step(params, opt_state, tokens)
            return params, st, loss

        return opt, broken

    return client, "make_sgd_batch_step", make

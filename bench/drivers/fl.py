"""Driver of ``fl.FLServer.run_round``: a closed loop of federated rounds
of a model at its published widths over a simulated NOMA cell.

The traffic's generator makes every client's corpus on the device from the
seed: a synthetic non-IID language task (one bigram Markov chain per topic,
Dirichlet topic mixtures per client) whose client sizes are one fixed set
(``client_sizes``), dealt out in a seed-drawn order, so every seed does
the same total work. The benchmark also makes the weights,
in one jitted call from the seed, and hands both to the server.

Set-up drives the server through its first ``setup_rounds`` rounds, the
first of which compiles, recording what each round selected, trained on
and produced; the window then runs the same server object. When the window
has closed, the same object runs one closing round from the seed's weights
(its compiled programs, random state, ages and buffers are the window's),
recorded the same way. The reference follows the set-up rounds and the
closing round, and the planner's admission in every round.

The records come from hooks on these names of the program (a run fails,
naming the one that is gone, where one is missing):
``FLServer.select`` (the channel each round's planner saw),
``FLServer.trainer.local_update`` (each client's batches, loss and delta),
``FLServer.trainer.step`` (the steps that ran, and the first gradient) and
``repro.fl.server.aggregate_deltas`` (the aggregate). Each round's
selection is the schedule ``run_round`` returns.
"""
from __future__ import annotations

import collections
import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench.reference import llama


def _nearest_prime(v: int, lo: int, hi: int) -> int:
    is_prime = lambda n: n > 1 and all(n % d for d in range(2, int(n ** .5) + 1))
    for d in range(hi - lo + 1):
        for c in (v - d, v + d):
            if lo <= c <= hi and is_prime(c):
                return c
    raise ValueError(f"no prime in [{lo}, {hi}]")


def client_sizes(tr: dict, n_clients: int, rng) -> np.ndarray:
    """Evenly spaced sizes over the mix's range, dealt in a seed-drawn
    order. With ``prime_sizes`` each is moved to the nearest prime in the
    range: the keys A_n * D_n / sum D of two clients of different prime
    sizes p < q can then be equal only at an age that q divides (101 or
    more over [100, 350]), so no admission turns on how the key rounds."""
    lo, hi = tr["samples_per_client"]
    grid = np.rint(np.linspace(lo, hi, n_clients)).astype(np.int64)
    if tr.get("prime_sizes"):
        grid = np.array([_nearest_prime(int(v), lo, hi) for v in grid])
    return rng.permutation(grid)


@functools.partial(jax.jit, static_argnames=(
    "n_clients", "total", "vocab", "seq_len", "n_topics", "conc", "alpha"))
def _corpus(key, owner, *, n_clients, total, vocab, seq_len, n_topics, conc,
            alpha):
    k_mat, k_mix, k_top, k_first, k_walk = jax.random.split(key, 5)
    mats = jax.random.dirichlet(k_mat, jnp.full((vocab,), conc),
                                shape=(n_topics, vocab))
    logm = jnp.log(mats + 1e-12)
    mix = jax.random.dirichlet(k_mix, jnp.full((n_topics,), alpha),
                               shape=(n_clients,))
    topic = jax.random.categorical(k_top, jnp.log(mix[owner] + 1e-12))
    first = jax.random.randint(k_first, (total,), 0, vocab)

    def walk(prev, k):
        nxt = jax.random.categorical(k, logm[topic, prev])
        return nxt, nxt

    _, rest = jax.lax.scan(walk, first, jax.random.split(k_walk, seq_len - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1).astype(
        jnp.int32), mix


def make_clients(tr: dict, n_clients: int, seed: int):
    """(sizes, [token array per client], topic mixes) from the seed."""
    ss = np.random.SeedSequence([seed, 1])
    rng = np.random.default_rng(ss)
    sizes = client_sizes(tr, n_clients, rng)
    owner = np.repeat(np.arange(n_clients), sizes)
    task = tr["task"]
    toks, mix = _corpus(jax.random.PRNGKey(int(rng.integers(2 ** 31 - 1))),
                        owner, n_clients=n_clients, total=int(sizes.sum()),
                        vocab=task["vocab_size"], seq_len=task["seq_len"],
                        n_topics=task["n_topics"],
                        conc=task["concentration"],
                        alpha=task["dirichlet_alpha"])
    toks, mix = np.asarray(toks), np.asarray(mix)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    return sizes, [toks[a:b] for a, b in zip(bounds[:-1], bounds[1:])], mix


def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


def _diff_norms(a, b):
    return jnp.stack([jnp.linalg.norm((x.astype(jnp.float32)
                                       - y.astype(jnp.float32)).ravel())
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


HOOKS = (("server", "select"), ("trainer", "local_update"),
         ("trainer", "step"), ("module", "aggregate_deltas"))


def admit(ages, sizes, gains, slots: int) -> np.ndarray:
    """The paper's admission in fp64: the ``slots`` clients of highest
    A_n * D_n / sum D, ties by gain, then by index."""
    prio = ages * (sizes / sizes.sum())
    order = np.lexsort((np.arange(len(sizes)), -gains, -prio))
    sel = np.zeros(len(sizes), bool)
    sel[order[:slots]] = True
    return sel


class Driver:
    unit = "round"

    def __init__(self, ctx):
        self.ctx = ctx
        self.c = ctx.config
        self.dep = ctx.config["deployment"]
        self.tr = ctx.traffic
        self.seed = ctx.seed
        self.rounds = []      # every round: gains, selection; full records
        self.first_grad = None
        self.full = True      # record batches, losses and norms
        self.steps = 0

    # -- set-up -----------------------------------------------------------
    def _model_cfg(self):
        from repro.configs import ModelConfig
        c = self.c
        return ModelConfig(
            name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            tie_embeddings=c["tie_word_embeddings"], glu=True,
            norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
            dtype=c["train_dtype"])

    def _weight_key(self):
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 2]))
        return jax.random.PRNGKey(int(rng.integers(2 ** 31 - 1)))

    def setup(self):
        import repro.fl.server as server_mod
        from repro.configs import FLConfig, NOMAConfig
        from repro.data import TaskConfig
        from repro.data.partition import ClientData

        d, tr = self.dep, self.tr
        self.sizes, corpora, mix = make_clients(tr, d["n_clients"], self.seed)
        self.row_owner = collections.defaultdict(set)
        self.row_count = []
        for ci, toks in enumerate(corpora):
            count = collections.Counter(r.tobytes() for r in toks)
            for r in count:
                self.row_owner[r].add(ci)
            self.row_count.append(count)
        clients = [ClientData(sequences=t, topic_mix=m)
                   for t, m in zip(corpora, mix)]
        fl = FLConfig(
            n_clients=d["n_clients"], local_batch=tr["local_batch"],
            local_epochs=tr["local_epochs"], lr=tr["lr"],
            samples_per_client=tuple(tr["samples_per_client"]),
            engine="jax", kernel_backend=tr["kernel_backend"],
            predictor="none", pairing=tr["pairing"],
            selection=tr["selection"], scenario=d["scenario"],
            cpu_cycles_per_sample=d["cpu_cycles_per_sample"],
            cpu_freq_range_ghz=tuple(d["cpu_freq_range_ghz"]),
            age_exponent=d["age_exponent"],
            seed=int(np.random.default_rng(np.random.SeedSequence(
                [self.seed, 3])).integers(2 ** 31 - 1)))
        ncfg = NOMAConfig(
            n_subchannels=d["n_subchannels"],
            users_per_subchannel=d["users_per_subchannel"],
            bandwidth_hz=d["bandwidth_hz"], noise_density=d["noise_density"],
            max_power_w=d["max_power_w"], path_loss_exp=d["path_loss_exp"],
            ref_path_loss=d["ref_path_loss"],
            cell_radius_m=d["cell_radius_m"], min_radius_m=d["min_radius_m"])
        task = TaskConfig(vocab_size=tr["task"]["vocab_size"],
                          seq_len=tr["task"]["seq_len"],
                          n_topics=tr["task"]["n_topics"])
        real_partition = server_mod.partition_clients
        server_mod.partition_clients = lambda fl, task: clients
        try:
            self.server = server_mod.FLServer(
                self._model_cfg(), fl, ncfg, task, policy=tr["policy"],
                engine="jax", predictor="none")
        finally:
            server_mod.partition_clients = real_partition
        params = llama.init_params(self._weight_key(), self.c)
        own = self.server.params
        if (jax.tree.structure(own) != jax.tree.structure(params)
                or [x.shape for x in jax.tree.leaves(own)]
                != [x.shape for x in jax.tree.leaves(params)]):
            raise SystemExit("bench: the program's parameter tree differs "
                             "from the reference layout")
        del own
        self.server.params = params
        self.slots = min(d["n_subchannels"] * d["users_per_subchannel"],
                         d["n_clients"])
        self.diff_norms = jax.jit(_diff_norms)
        self.leaf_norms = jax.jit(_leaf_norms)
        self._hook(server_mod)
        for _ in range(tr["setup_rounds"]):
            before = self.server.params
            self._round("chain")
            self.rounds[-1]["update"] = np.asarray(
                self.diff_norms(self.server.params, before))
        self.final_change = np.asarray(self.diff_norms(
            self.server.params, llama.init_params(self._weight_key(),
                                                  self.c)))
        jax.block_until_ready(self.server.params)
        self.full = False

    def _round(self, start=None):
        """One ``run_round``; the selection is the schedule it returns."""
        self.steps = 0
        sched = self.server.run_round()
        jax.block_until_ready(self.server.params)
        rec = self.rounds[-1]
        rec["selected"] = np.array(sched.selected, bool)
        if start is not None:
            rec["start"] = start

    def _hook(self, server_mod):
        """Hooks that record, for each round, the channel the planner saw
        and the steps that ran; while ``self.full``, also each client's
        batches, loss and delta norms, the first gradient and the
        aggregate's norms."""
        srv = self.server
        owners = {"server": srv, "trainer": getattr(srv, "trainer", None),
                  "module": server_mod}
        for owner, attr in HOOKS:
            if not hasattr(owners[owner], attr):
                names = ", ".join(f"{o}.{a}" for o, a in HOOKS)
                raise SystemExit(
                    f"bench: the fl driver records rounds through {names}; "
                    f"{owner}.{attr} is missing from the program")
        real_select = srv.select
        real_local = srv.trainer.local_update
        real_step = srv.trainer.step
        real_agg = server_mod.aggregate_deltas
        lr = self.tr["lr"]

        def step(params, opt_state, tokens):
            self.steps += 1
            out = real_step(params, opt_state, tokens)
            if self.full and self.first_grad is None:  # -(p1 - p0) / lr
                self.first_grad = np.asarray(
                    self.diff_norms(out[0], params)) / lr
            return out

        def select(env):
            self.rounds.append({"gains": np.array(env.gains, np.float64)})
            return real_select(env)

        def local_update(params, batches):
            if not self.full:
                return real_local(params, batches)
            batches = [np.asarray(b) for b in batches]
            delta, loss = real_local(params, batches)
            self.rounds[-1].setdefault("clients", []).append({
                "batches": batches, "loss": float(loss),
                "norms": np.asarray(self.leaf_norms(delta))})
            return delta, loss

        def aggregate(deltas, weights, *, impl):
            out = real_agg(deltas, weights, impl=impl)
            if self.full:
                self.rounds[-1]["aggregate"] = np.asarray(
                    self.leaf_norms(out))
            return out

        srv.select = select
        srv.trainer.local_update = local_update
        srv.trainer.step = step
        server_mod.aggregate_deltas = aggregate
        self._unhook = lambda: setattr(server_mod, "aggregate_deltas",
                                       real_agg)

    # -- window -----------------------------------------------------------
    def run_unit(self) -> dict:
        self._round()
        return {"work": 1, "steps": self.steps}

    def close(self):
        """After the window: the closing round, from the seed's weights,
        recorded in full; then the program's state is freed."""
        srv = self.server
        srv.params = None
        gc.collect()
        srv.params = llama.init_params(self._weight_key(), self.c)
        self.full = True
        self._round("seed")
        self.rounds[-1]["update"] = np.asarray(self.diff_norms(
            srv.params, llama.init_params(self._weight_key(), self.c)))
        self._unhook()
        self.server = None
        gc.collect()

    # -- comparison -------------------------------------------------------
    def owners(self, rec) -> list:
        """The client whose own corpus each recorded client trained on,
        or -1 where its batches are not exactly one pass per local epoch
        over floor(size / local_batch) batches of distinct rows of one
        selected client's corpus."""
        b, e = self.tr["local_batch"], self.tr["local_epochs"]
        used, out = set(), []
        for cl in rec.get("clients", []):
            rows = [r.tobytes() for x in cl["batches"] for r in x]
            cand = (set.intersection(*(self.row_owner.get(r, set())
                                       for r in rows)) if rows else set())
            cand = sorted(ci for ci in cand
                          if rec["selected"][ci] and ci not in used)
            ci = cand[0] if cand else -1
            n = (int(self.sizes[ci]) // b) * e if ci >= 0 else 0
            ok = (ci >= 0 and len(cl["batches"]) == n
                  and all(x.shape[0] == b for x in cl["batches"]))
            if ok:
                per = n // e
                for k in range(e):
                    count = collections.Counter(
                        r.tobytes() for x in cl["batches"][k * per:
                                                           (k + 1) * per]
                        for r in x)
                    ok &= all(v <= self.row_count[ci][r]
                              for r, v in count.items())
            used.add(ci)
            out.append(ci if ok else -1)
        return out

    def batch_mismatch(self) -> int:
        """Recorded clients whose batches are not their own corpus's, plus
        selected clients that trained on nothing, over the fully recorded
        rounds."""
        bad = 0
        for rec in self.rounds:
            if "start" not in rec:
                continue
            own = self.owners(rec)
            bad += sum(o < 0 for o in own)
            bad += abs(int(rec["selected"].sum()) - len(own))
        return bad

    def follow(self, dtype) -> dict:
        """The reference's own pass in ``dtype``: its admission in every
        round from the channel the planner saw; for each fully recorded
        round, from the same weights (the chain of set-up rounds, or the
        seed's for the closing round) and batches, each client's mean loss
        and delta norms, the aggregate's and the update's norms; the norms
        of the total change after the set-up rounds."""
        c, lr = self.c, self.tr["lr"]
        p0 = llama.init_params(self._weight_key(), c, dtype)
        first = next(r for r in self.rounds if "clients" in r)
        first_grad = np.asarray(_leaf_norms(llama.grad(
            p0, jnp.asarray(first["clients"][0]["batches"][0]),
            cfg=llama._cfg(c))))
        ages = np.ones(len(self.sizes))
        sizes = self.sizes.astype(np.float64)
        params, rounds = p0, []
        for rec in self.rounds:
            sel = admit(ages, sizes, rec["gains"], self.slots)
            ages = np.where(sel, 1.0, ages + 1.0)
            out = {"selected": sel}
            rounds.append(out)
            if "start" not in rec:
                continue
            start = (params if rec["start"] == "chain"
                     else llama.init_params(self._weight_key(), c, dtype))
            own = [o if o >= 0 else int(i) for o, i in zip(
                self.owners(rec), np.flatnonzero(rec["selected"]))]
            w = (sizes[own] / sizes[own].sum()).astype(np.float32)
            agg, clients = None, []
            for wi, cl in zip(w, rec.get("clients", [])):
                delta, loss = llama.local_update(start, cl["batches"], c, lr)
                clients.append({"loss": loss,
                                "norms": np.asarray(_leaf_norms(delta))})
                agg = (jax.tree.map(lambda d: wi * d, delta) if agg is None
                       else jax.tree.map(lambda a, d: a + wi * d, agg, delta))
                del delta
            new = jax.tree.map(lambda p, a: (p.astype(jnp.float32) + a)
                               .astype(p.dtype), start, agg)
            out.update(clients=clients,
                       aggregate=np.asarray(_leaf_norms(agg)),
                       update=np.asarray(_diff_norms(new, start)))
            if rec["start"] == "chain":
                params = new
            del agg, new, start
        p0 = llama.init_params(self._weight_key(), c, dtype)
        return {"rounds": rounds, "first_grad": first_grad,
                "final_change": np.asarray(_diff_norms(params, p0))}

    def program(self) -> dict:
        """What the program produced in the same rounds, as ``follow``
        shapes it."""
        return {"rounds": self.rounds, "first_grad": self.first_grad,
                "final_change": self.final_change}

    @staticmethod
    def readings(got: dict, ref: dict) -> dict:
        mismatch, loss_gap, delta_gap, agg_gap, upd_gap = 0, 0.0, 0.0, 0.0, 0.0
        for g, r in zip(got["rounds"], ref["rounds"]):
            mismatch += int(np.any(g["selected"] != r["selected"]))
            if "clients" not in r:
                continue
            mismatch += int(len(g.get("clients", [])) != len(r["clients"]))
            for gc_, rc in zip(g.get("clients", []), r["clients"]):
                loss_gap = max(loss_gap, abs(gc_["loss"] - rc["loss"])
                               / max(abs(rc["loss"]), 1e-12))
                delta_gap = max(delta_gap, compare.norm_gap(gc_["norms"],
                                                            rc["norms"]))
            agg_gap = max(agg_gap, compare.norm_gap(
                g.get("aggregate", np.zeros_like(r["aggregate"])),
                r["aggregate"]))
            upd_gap = max(upd_gap, compare.norm_gap(g["update"], r["update"]))
        upd_gap = max(upd_gap, compare.norm_gap(got["final_change"],
                                                ref["final_change"]))
        return {"selection_mismatch": mismatch, "loss_rel": loss_gap,
                "grad_norm_gap": compare.norm_gap(got["first_grad"],
                                                  ref["first_grad"]),
                "delta_norm_gap": delta_gap, "aggregate_norm_gap": agg_gap,
                "update_norm_gap": upd_gap,
                "rounds_compared": len(ref["rounds"]),
                "rounds_trained": sum("clients" in r for r in ref["rounds"])}

    def numbers(self, dtype=None) -> dict:
        """Readings of the program against the float32 reference; with
        ``dtype``, of the reference in that precision (the control)."""
        ref = self.follow(jnp.float32)
        got = self.program() if dtype is None else self.follow(dtype)
        out = self.readings(got, ref)
        out["batch_mismatch"] = self.batch_mismatch()
        return out

"""Plain reference of the Monte-Carlo wireless sweep: the scenario's
state transitions, the paper's age-priority admission, strong/weak NOMA
pairing, closed-form max-min power, SIC rates, round time and the age
update, written from their published definitions in straightforward
``jax.numpy`` (arXiv:2304.08996 sections III-IV; scenario processes as the
deployment's configuration file states them).

It imports nothing of the system under test. It draws the same random
numbers from the same seed, in the order of the sweep's documented key
schedule: ``PRNGKey(seed)`` splits into an init key and a roll key; the
init key splits seven ways (position, speed, heading or waypoint, fading,
shadowing, cpu, data size); the roll key splits into one key per round,
and each round's key five ways (fading, shadowing, mobility, cpu, data).

``dtype`` is the precision every computation runs in: float32 as the
configuration states, or bfloat16 for the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def bessel_j0(x: float) -> float:
    """J0 by its power series (exact to double precision for |x| < 10)."""
    total, term, k = 0.0, 1.0, 0
    while abs(term) > 1e-17:
        total += term
        k += 1
        term *= -(x / 2.0) ** 2 / (k * k)
    return total


def _annulus(key, shape, r_min, r_max):
    k_r, k_th = jax.random.split(key)
    r = jnp.sqrt(jax.random.uniform(k_r, shape, minval=r_min ** 2,
                                    maxval=r_max ** 2))
    th = jax.random.uniform(k_th, shape, minval=0.0, maxval=2.0 * np.pi)
    return r, th


@functools.partial(jax.jit, static_argnames=("dep", "s", "n", "dtype"))
def init_state(key, *, dep, s, n, dtype):
    """Initial deployment state of ``s`` independent drops of ``n``
    clients. ``dep`` is the hashable deployment tuple of ``params``."""
    p = dict(dep)
    k_pos, k_v, k_aux, k_fade, k_sh, k_cpu, k_ns = jax.random.split(key, 7)
    shape = (s, n)
    r, th = _annulus(k_pos, shape, p["min_radius_m"], p["cell_radius_m"])
    r, th = r.astype(dtype), th.astype(dtype)
    x, y = r * jnp.cos(th), r * jnp.sin(th)
    speed = jax.random.uniform(k_v, shape, minval=p["v_min"],
                               maxval=p["v_max"]).astype(dtype)
    if p["mobility"] == "drift":
        hd = jax.random.uniform(k_aux, shape, minval=0.0,
                                maxval=2.0 * np.pi).astype(dtype)
        ax, ay = speed * jnp.cos(hd), speed * jnp.sin(hd)
    else:   # waypoint: the target position
        wr, wth = _annulus(k_aux, shape, p["min_radius_m"],
                           p["cell_radius_m"])
        wr, wth = wr.astype(dtype), wth.astype(dtype)
        ax, ay = wr * jnp.cos(wth), wr * jnp.sin(wth)
    h = (jax.random.normal(k_fade, shape + (2,)) * np.sqrt(0.5)).astype(dtype)
    shadow = (jax.random.normal(k_sh, shape).astype(dtype)
              * jnp.asarray(p["shadow_sigma_db"], dtype))
    cpu = jax.random.uniform(k_cpu, shape, minval=p["cpu_lo"],
                             maxval=p["cpu_hi"]).astype(dtype)
    size = jax.random.uniform(k_ns, shape, minval=p["ns_lo"],
                              maxval=p["ns_hi"]).astype(dtype)
    return dict(x=x, y=y, ax=ax, ay=ay, speed=speed, h=h, shadow=shadow,
                cpu=cpu, size=size)


def _move(st, key, p, dtype):
    """One mobility step: vehicular drift reflected at both boundary
    circles, or random waypoint."""
    x, y, ax, ay, speed = st["x"], st["y"], st["ax"], st["ay"], st["speed"]
    dt = jnp.asarray(p["move_s"], dtype)
    r_min = jnp.asarray(p["min_radius_m"], dtype)
    r_max = jnp.asarray(p["cell_radius_m"], dtype)
    if p["mobility"] == "drift":
        x2, y2 = x + ax * dt, y + ay * dt
        r = jnp.sqrt(x2 * x2 + y2 * y2)
        hit = (r > r_max) | (r < r_min)
        scale = jnp.clip(r, r_min, r_max) / jnp.maximum(r, 1e-9)
        x2 = jnp.where(hit, x2 * scale, x2)
        y2 = jnp.where(hit, y2 * scale, y2)
        return dict(st, x=x2, y=y2, ax=jnp.where(hit, -ax, ax),
                    ay=jnp.where(hit, -ay, ay))
    k_wp, k_v = jax.random.split(key)
    dx, dy = ax - x, ay - y
    d = jnp.sqrt(dx * dx + dy * dy)
    step = speed * dt
    arrived = d <= step
    ux, uy = dx / jnp.maximum(d, 1e-9), dy / jnp.maximum(d, 1e-9)
    x2 = jnp.where(arrived, ax, x + ux * step)
    y2 = jnp.where(arrived, ay, y + uy * step)
    wr, wth = _annulus(k_wp, x.shape, p["min_radius_m"], p["cell_radius_m"])
    wr, wth = wr.astype(dtype), wth.astype(dtype)
    v_new = jax.random.uniform(k_v, x.shape, minval=p["v_min"],
                               maxval=p["v_max"]).astype(dtype)
    return dict(st, x=x2, y=y2,
                ax=jnp.where(arrived, wr * jnp.cos(wth), ax),
                ay=jnp.where(arrived, wr * jnp.sin(wth), ay),
                speed=jnp.where(arrived, v_new, speed))


@functools.partial(jax.jit, static_argnames=("dep", "dtype"))
def round_step(st, ages, part, key, model_bits, *, dep, dtype):
    """One round over all drops: advance the environment, admit the
    ``slots`` clients of highest age priority, pair, allocate, time, and
    age. Returns the new state, ages, participation and the round's
    (t_round, t_cmp, t_com of the bottleneck client) per drop."""
    p = dict(dep)
    k_fade, k_sh, k_mob, _k_cpu, _k_ns = jax.random.split(key, 5)
    st = _move(st, k_mob, p, dtype)
    dist = jnp.maximum(jnp.sqrt(st["x"] ** 2 + st["y"] ** 2),
                       jnp.asarray(p["min_radius_m"], dtype))
    # Gauss-Markov Rayleigh fading and Gudmundson shadowing
    rho = jnp.asarray(p["rho_fading"], dtype)
    w = (jax.random.normal(k_fade, st["h"].shape) * np.sqrt(0.5)).astype(dtype)
    h = rho * st["h"] + jnp.asarray(math.sqrt(1.0 - p["rho_fading"] ** 2),
                                    dtype) * w
    fpow = h[..., 0] * h[..., 0] + h[..., 1] * h[..., 1]
    rho_s = jnp.exp(-st["speed"] * jnp.asarray(p["move_s"] / p["shadow_decorr_m"],
                                               dtype))
    z = jax.random.normal(k_sh, st["shadow"].shape).astype(dtype)
    shadow = (rho_s * st["shadow"] + jnp.sqrt(1.0 - rho_s * rho_s)
              * jnp.asarray(p["shadow_sigma_db"], dtype) * z)
    gains = (jnp.asarray(p["ref_path_loss"], dtype)
             * dist ** jnp.asarray(-p["path_loss_exp"], dtype) * fpow
             * jnp.asarray(10.0, dtype) ** (shadow / 10.0))
    st = dict(st, h=h, shadow=shadow)

    size, cpu = st["size"], st["cpu"]
    s, n = gains.shape
    slots = min(p["slots"], n)
    # age priority A * D_n / sum D; ties by gain, then by index
    prio = ages * (size / jnp.sum(size, axis=1, keepdims=True))
    idx = jnp.broadcast_to(jnp.arange(n), (s, n))
    order = jnp.lexsort((idx, -gains, -prio), axis=1)
    cand = order[:, :slots]                                  # (s, slots)
    g = jnp.take_along_axis(gains, cand, axis=1)
    # strong/weak pairing: i-th strongest with i-th weakest
    rank = jnp.lexsort((cand, -g), axis=1)
    cand = jnp.take_along_axis(cand, rank, axis=1)
    g = jnp.take_along_axis(g, rank, axis=1)
    t_cmp_all = (jnp.asarray(p["local_epochs"] * p["cycles_per_sample"], dtype)
                 * size / cpu)
    t_cmp = jnp.take_along_axis(t_cmp_all, cand, axis=1)
    bw = jnp.asarray(p["bandwidth_hz"], dtype)
    n0b = jnp.asarray(p["noise_density"] * p["bandwidth_hz"], dtype)
    pmax = jnp.asarray(p["max_power_w"], dtype)
    m = slots // 2
    gi, gj = g[:, :m], g[:, slots - 1:slots - 1 - m:-1] if m else g[:, :0]
    # max-min power: strong user at P_max, weak user's received power y the
    # positive root of y^2 + N y - P g_i N = 0 (conjugate form)
    y = 2.0 * pmax * gi * n0b / (n0b + jnp.sqrt(n0b * n0b
                                                + 4.0 * pmax * gi * n0b))
    pj = jnp.minimum(y / jnp.maximum(gj, 1e-30), pmax)
    r_i = bw * jnp.log1p(pmax * gi / (pj * gj + n0b)) / np.log(2.0)
    r_j = bw * jnp.log1p(pj * gj / n0b) / np.log(2.0)
    rates = jnp.concatenate([r_i, r_j[:, ::-1]], axis=1)
    if slots % 2:   # the weakest candidate alone on its subchannel
        solo = bw * jnp.log1p(pmax * g[:, -1:] / n0b) / np.log(2.0)
        rates = jnp.concatenate([rates, solo], axis=1)
    t_com = model_bits.astype(dtype) / jnp.maximum(rates, 1e-9)
    t_tot = t_cmp + t_com
    b = jnp.argmax(t_tot, axis=1)
    take = lambda a: jnp.take_along_axis(a, b[:, None], axis=1)[:, 0]
    sel = jnp.zeros((s, n), bool).at[jnp.arange(s)[:, None], cand].set(True)
    ages2 = jnp.where(sel, 1.0, ages + 1.0).astype(dtype)
    return (st, ages2, part + sel.astype(dtype), take(t_tot), take(t_cmp),
            take(t_com))


def params(config: dict) -> tuple:
    """The hashable deployment tuple the jitted steps take."""
    d = config["deployment"]
    sc = d["scenario"]
    out = dict(
        slots=d["n_subchannels"] * d["users_per_subchannel"],
        bandwidth_hz=d["bandwidth_hz"], noise_density=d["noise_density"],
        max_power_w=d["max_power_w"], path_loss_exp=d["path_loss_exp"],
        ref_path_loss=d["ref_path_loss"], cell_radius_m=d["cell_radius_m"],
        min_radius_m=d["min_radius_m"],
        cycles_per_sample=d["cpu_cycles_per_sample"],
        local_epochs=d["local_epochs"],
        cpu_lo=d["cpu_freq_range_ghz"][0] * 1e9,
        cpu_hi=d["cpu_freq_range_ghz"][1] * 1e9,
        ns_lo=float(d["samples_per_client"][0]),
        ns_hi=float(d["samples_per_client"][1]),
        mobility=sc["mobility"], v_min=sc["speed_mps"][0],
        v_max=sc["speed_mps"][1], move_s=sc["move_s"],
        rho_fading=bessel_j0(2.0 * np.pi * sc["doppler_hz"] * sc["slot_s"]),
        shadow_sigma_db=sc["shadow_sigma_db"],
        shadow_decorr_m=sc["shadow_decorr_m"])
    return tuple(sorted(out.items()))


def sweep(dep: tuple, seed: int, n_seeds: int, n_clients: int, rounds: int,
          model_bits: float, dtype=jnp.float32) -> dict:
    """One sweep of ``rounds`` rounds over ``n_seeds`` drops; host arrays
    of the per-round round time and bottleneck split and the final ages
    and participation."""
    k_init, k_roll = jax.random.split(jax.random.PRNGKey(seed))
    st = init_state(k_init, dep=dep, s=n_seeds, n=n_clients, dtype=dtype)
    keys = jax.random.split(k_roll, rounds)
    ages = jnp.ones((n_seeds, n_clients), dtype)
    part = jnp.zeros((n_seeds, n_clients), dtype)
    mb = jnp.asarray(model_bits, jnp.float32)
    t, tc, tu = [], [], []
    for i in range(rounds):
        st, ages, part, t_r, t_c, t_u = round_step(st, ages, part, keys[i],
                                                   mb, dep=dep, dtype=dtype)
        t.append(t_r)
        tc.append(t_c)
        tu.append(t_u)
    f32 = lambda a: np.asarray(jnp.stack(a).astype(jnp.float32))
    return {"t_round": f32(t), "t_comp_bottleneck": f32(tc),
            "t_up_bottleneck": f32(tu),
            "final_ages": np.asarray(ages.astype(jnp.float32)),
            "participation": np.asarray(part.astype(jnp.float32))}

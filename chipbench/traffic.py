"""The one traffic generator: per-target metric rows shaped like the
NASA-KSC trace, read from a mix's parameters in ``traffic/<name>.json``.

The shape is copied from ``src/repro/workloads/nasa.py`` (``nasa_trace``):
a diurnal swing ``1 + a sin(2 pi (tod - 0.33))``, AR(1) momentum in log
space, and bursts of a few times the base load with a linear onset ramp.
That file makes one series of per-minute request counts; here every one of
Z targets gets its own series, one row per control tick, with its level
and phase of day drawn from the seed.  The key metric (CPU) carries the
shape; the other four metrics are proportional to it with log-normal noise.

The rows are made on the device in one jitted call (set-up time is paid by
every run), in float32, and returned as float64 host arrays.  Every seed
gets the same sizes: the same number of targets, history rows, pool rows
and burst slots, so the work per tick does not depend on the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DAY_S = 86400.0


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _rows(key, Z: int, T: int, n_metrics: int, slots: int, p: dict):
    ks = jax.random.split(key, 10)
    level = jnp.exp(jax.random.uniform(ks[0], (Z,), minval=jnp.log(
        p["level"][0]), maxval=jnp.log(p["level"][1])))
    phase = jax.random.uniform(ks[1], (Z,))                 # tod at t = 0
    tt = jnp.arange(T, dtype=jnp.float32)[:, None]
    tod = (phase[None, :] + tt * p["interval_s"] / DAY_S) % 1.0
    diurnal = 1.0 + p["diurnal_amp"] * jnp.sin(2 * jnp.pi * (tod - 0.33))

    # AR(1) momentum in log space, started from its stationary law
    a, sig = p["ar_coef"], p["ar_sigma"]
    ar0 = jax.random.normal(ks[2], (Z,)) * sig / jnp.sqrt(1.0 - a * a)
    eps = jax.random.normal(ks[3], (T - 1, Z)) * sig
    _, ar = jax.lax.scan(lambda x, e: (a * x + e, a * x + e), ar0, eps)
    ar = jnp.concatenate([ar0[None], ar], axis=0)

    # bursts: a Poisson number per target (at most ``slots``), each at a
    # uniform start with a gain of g_lo..g_hi times the base, a linear
    # onset ramp and a length of len_lo..len_hi ticks
    rate = p["bursts_per_day"] * T * p["interval_s"] / DAY_S
    n = jax.random.poisson(ks[4], rate, (Z,))
    live = jnp.arange(slots)[None, :] < n[:, None]          # (Z, K)
    start = jax.random.randint(ks[5], (Z, slots), 0, T)
    blen = jax.random.randint(ks[6], (Z, slots), p["burst_len"][0],
                              p["burst_len"][1] + 1)
    gain = jax.random.uniform(ks[7], (Z, slots), minval=p["gain"][0],
                              maxval=p["gain"][1]) - 1.0
    burst = jnp.zeros((T, Z))
    for j in range(slots):                # one (T, Z) slab per slot
        k = tt - start[:, j][None, :].astype(jnp.float32)
        on = live[:, j][None, :] & (k >= 0) & (k < blen[:, j][None, :])
        burst += jnp.where(on, gain[:, j] * jnp.minimum((k + 1.0) / p["ramp"],
                                                        1.0), 0.0)

    cpu = level[None, :] * diurnal * jnp.exp(ar) * (1.0 + burst)
    noise = jnp.exp(jax.random.normal(ks[8], (T, Z, n_metrics - 1))
                    * p["noise_sigma"])
    scale = p["metric_scale"]
    return jnp.concatenate([cpu[:, :, None] * scale[0],
                            cpu[:, :, None] * scale[1:] * noise], axis=-1)


def generate(mix: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(history (Th, Z, M), pool (Tp, Z, M))`` float64 metric rows.

    ``history`` is the hour before the run, from which scalers are fitted
    and the forecast window is first filled; ``pool`` is cycled through by
    the measured ticks, one row batch per tick."""
    Z = int(mix["targets"])
    Th, Tp = int(mix["history_ticks"]), int(mix["pool_ticks"])
    scale = np.asarray(mix["metric_scale"], np.float32)
    p = {"level": jnp.asarray(mix["level"], jnp.float32),
         "interval_s": float(mix["interval_s"]),
         "diurnal_amp": float(mix["diurnal_amp"]),
         "ar_coef": float(mix["ar_coef"]), "ar_sigma": float(mix["ar_sigma"]),
         "bursts_per_day": float(mix["bursts_per_day"]),
         "burst_len": tuple(int(x) for x in mix["burst_len_ticks"]),
         "gain": jnp.asarray(mix["burst_gain"], jnp.float32),
         "ramp": float(mix["burst_ramp_ticks"]),
         "noise_sigma": float(mix["noise_sigma"]),
         "metric_scale": jnp.asarray(scale)}
    # burst slots: the mean count over the rows plus six deviations, so
    # that the cap binds on no target in practice
    rate = p["bursts_per_day"] * (Th + Tp) * p["interval_s"] / DAY_S
    slots = int(np.ceil(rate + 6.0 * np.sqrt(rate) + 1.0))
    # a 32-bit key from any whole-number seed (seeds may exceed 2**32)
    k = int(np.random.default_rng([int(seed), 1]).integers(0, 2**31 - 1))
    rows = np.asarray(_rows(jax.random.PRNGKey(k), Z, Th + Tp, scale.size,
                            slots, p), np.float64)
    return rows[:Th], rows[Th:]

"""Seeded inputs for the benchmark's cells, drawn on the device.

Every array is a pure function of ``(seed, stream, index)``: the same
seed gives the same data, and another job or tick index gives other
data.  ``seed`` may be any whole number up to 2**63; it is mixed with
``stream`` by ``numpy.random.SeedSequence`` before it keys JAX's
generator, whose own key keeps only 32 bits of a large seed.

The value distributions follow ``make_dist`` in the repository's CPU
benchmarks (uniform, zipf, bimodal, sorted over [-1e9, 1e9]); ``zipf`` is
drawn as a discretised Pareto with the same tail exponent, since JAX has
no Zipf sampler.  ``lognormal`` gives latency-like series for the
percentile service.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SPAN = 1e9        # job values lie in [-SPAN, SPAN)
ZIPF_A = 2.5


def key(seed: int, stream: str) -> jax.Array:
    """The generator key of one named stream of a run."""
    words = [int(b) for b in stream.encode()]
    state = np.random.SeedSequence([int(seed) & (2**64 - 1), *words])
    return jax.random.key(int(state.generate_state(1)[0]))


def _uniform(k, shape):
    return jax.random.uniform(k, shape, jnp.float32, -SPAN, SPAN)


def _zipf(k, shape):
    z = jnp.floor(jax.random.pareto(k, ZIPF_A - 1.0, shape, jnp.float32))
    z = jnp.minimum(z, 2.0 ** 31)
    return (jnp.mod(z, 2_000_003.0) * 1e3 - SPAN).astype(jnp.float32)


def _bimodal(k, shape):
    ka, kb, kp = jax.random.split(k, 3)
    a = -3.33e8 + 1.66e8 * jax.random.normal(ka, shape, jnp.float32)
    b = 3.33e8 + 1.66e8 * jax.random.normal(kb, shape, jnp.float32)
    pick = jax.random.uniform(kp, shape) < 0.5
    return jnp.clip(jnp.where(pick, a, b), -SPAN, SPAN)


def _sorted(k, shape):
    parts, n_i = shape
    lo = jnp.linspace(-SPAN, SPAN, parts + 1, dtype=jnp.float32)
    u = jax.random.uniform(k, shape, jnp.float32)
    x = lo[:-1, None] + u * (lo[1:] - lo[:-1])[:, None]
    return jnp.sort(x, axis=1)


DISTRIBUTIONS = {"uniform": _uniform, "zipf": _zipf, "bimodal": _bimodal,
                 "sorted": _sorted}


@functools.partial(jax.jit, static_argnames=("dist", "shape"))
def job_values(k: jax.Array, index, *, dist: str, shape: tuple) -> jax.Array:
    """The (P, n_i) float32 input of job ``index``."""
    return DISTRIBUTIONS[dist](jax.random.fold_in(k, index), shape)


@functools.partial(jax.jit, static_argnames=("series", "per_tick", "sigma",
                                             "log_median"))
def ticks(k: jax.Array, indices: jax.Array, *, series: int, per_tick: int,
          sigma: float, log_median: tuple) -> jax.Array:
    """(T, S, L) float32 lognormal observations of ticks ``indices``.

    Series s has the log-median ``mu_s``, drawn once per seed uniform in
    ``log_median``; tick t adds ``sigma`` times standard normal noise
    drawn from its own key, folded from ``t``, so a tick's values do not
    depend on which other ticks are drawn with it."""
    k_mu, k_tick = jax.random.split(k)
    lo, hi = log_median
    mu = jax.random.uniform(k_mu, (series, 1), jnp.float32, lo, hi)

    def one(t):
        z = jax.random.normal(jax.random.fold_in(k_tick, t),
                              (series, per_tick), jnp.float32)
        return jnp.exp(mu + sigma * z)
    return jax.vmap(one)(indices)

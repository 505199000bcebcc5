"""The seeded generators."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import datagen

SHAPE = (3, 512)


@pytest.mark.parametrize("dist", sorted(datagen.DISTRIBUTIONS))
def test_same_seed_same_data(dist):
    a = datagen.job_values(datagen.key(2**31 + 7, "job"), 4, dist=dist,
                           shape=SHAPE)
    b = datagen.job_values(datagen.key(2**31 + 7, "job"), 4, dist=dist,
                           shape=SHAPE)
    assert a.dtype == jnp.float32 and a.shape == SHAPE
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.isnan(np.asarray(a)).any()


@pytest.mark.parametrize("dist", sorted(datagen.DISTRIBUTIONS))
def test_other_jobs_other_data(dist):
    k = datagen.key(11, "job")
    a = np.asarray(datagen.job_values(k, 0, dist=dist, shape=SHAPE))
    b = np.asarray(datagen.job_values(k, 1, dist=dist, shape=SHAPE))
    assert not np.array_equal(a, b)


def test_large_seeds_stay_apart():
    """JAX's own key keeps 32 bits of a seed; ``key`` keeps them all."""
    a = datagen.job_values(datagen.key(5, "job"), 0, dist="uniform",
                           shape=SHAPE)
    b = datagen.job_values(datagen.key(2**33 + 5, "job"), 0,
                           dist="uniform", shape=SHAPE)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_streams_differ():
    a = datagen.job_values(datagen.key(5, "job"), 0, dist="uniform",
                           shape=SHAPE)
    b = datagen.job_values(datagen.key(5, "warmup"), 0, dist="uniform",
                           shape=SHAPE)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_ticks_do_not_depend_on_how_they_are_drawn():
    kw = dict(series=6, per_tick=4, sigma=0.6, log_median=(-6.9, 0.0))
    k = datagen.key(3, "ticks")
    together = np.asarray(datagen.ticks(k, jnp.arange(0, 5), **kw))
    apart = np.concatenate([np.asarray(datagen.ticks(k, jnp.arange(0, 2), **kw)),
                            np.asarray(datagen.ticks(k, jnp.arange(2, 5), **kw))])
    np.testing.assert_array_equal(together, apart)
    assert (together > 0).all()
    assert not np.array_equal(together[0], together[1])


def test_sorted_partitions_are_sorted_and_ordered():
    x = np.asarray(datagen.job_values(datagen.key(1, "job"), 0,
                                      dist="sorted", shape=SHAPE))
    assert (np.diff(x, axis=1) >= 0).all()
    assert (x[:-1, -1] <= x[1:, 0]).all()

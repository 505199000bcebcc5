"""Engine-wide oracle grid (ISSUE 5): every quantile engine vs the
``np.partition`` oracle across dtype x distribution x shard count.

The grid itself (cases, oracles, rank rules) lives in ``tests/_grid.py`` —
a future engine gets the whole surface by adding one runner here.  All
assertions are BIT-exact.

In-process runners cover the single-process engines (``gk_select``,
``gk_select_multi``, the warm/cold service path, the grouped engine) with
the shard count played by pseudo-partitions / ragged ingest chunks;
subprocess runners cover the shard_map engines (``distributed_quantile``
single/multi) on real 1/3/6-device meshes.  float64 cells run under x64
(scoped ``jax.enable_x64(True)`` in-process; a global switch in the
subprocesses).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _grid import (DTYPES, DISTRIBUTIONS, SHARD_COUNTS, QS, make_case,
                   needs_x64, x64_ctx, oracle_kth, oracle_quantile,
                   grouped_oracle, ragged_chunks, target_rank)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 3072                       # divisible by every shard count in the grid



def _cells():
    for dtype in DTYPES:
        for dist in DISTRIBUTIONS:
            yield dtype, dist


@pytest.mark.parametrize("dtype,dist", list(_cells()))
@pytest.mark.parametrize("parts", SHARD_COUNTS)
class TestLocalEngines:
    def test_gk_select_and_multi(self, dtype, dist, parts):
        from repro.core import gk_select, gk_select_multi
        x = make_case(dist, dtype, N)
        with x64_ctx(dtype):
            xp = jnp.asarray(x).reshape(parts, -1)
            for q in QS:
                want = oracle_quantile(x, q)
                got = np.asarray(jax.device_get(gk_select(xp, q)))
                assert got == want, (dtype, dist, parts, q, got, want)
            got_m = np.asarray(jax.device_get(gk_select_multi(xp, QS)))
            wants = [oracle_quantile(x, q) for q in QS]
            assert list(got_m) == wants, (dtype, dist, parts)


@pytest.mark.parametrize("dtype,dist", list(_cells()))
@pytest.mark.parametrize("parts", SHARD_COUNTS)
class TestServiceWarmPath:
    def test_warm_exact_matches_oracle(self, dtype, dist, parts):
        from repro.launch import QuantileService
        x = make_case(dist, dtype, N, seed=1)
        with x64_ctx(dtype):
            svc = QuantileService(eps=0.02, dtype=jnp.dtype(dtype))
            for c in ragged_chunks(x, parts, seed=parts):
                svc.ingest("grid", c)
            for q in QS:
                want = oracle_quantile(x, q)
                warm = np.asarray(jax.device_get(svc.exact("grid", q)))
                cold = np.asarray(jax.device_get(
                    svc.exact("grid", q, warm=False)))
                assert warm == want, (dtype, dist, parts, q, warm, want)
                assert cold == want, (dtype, dist, parts, q, cold, want)


@pytest.mark.parametrize("dtype,dist", list(_cells()))
@pytest.mark.parametrize("parts", SHARD_COUNTS)
class TestGroupedEngine:
    G = 4

    def test_grouped_matches_per_group_oracle(self, dtype, dist, parts):
        from repro.core import gk_select_grouped, local_ops
        x = make_case(dist, dtype, N, seed=2)
        rng = np.random.default_rng(3)
        keys = rng.integers(0, self.G, size=N).astype(np.int32)
        with x64_ctx(dtype):
            got = np.asarray(jax.device_get(gk_select_grouped(
                jnp.asarray(x).reshape(parts, -1),
                jnp.asarray(keys).reshape(parts, -1), QS,
                num_groups=self.G)))
            _, hi = local_ops._sentinels(jnp.asarray(x).dtype)
            hi = np.asarray(hi)
            for g in range(self.G):
                for qi, q in enumerate(QS):
                    want = grouped_oracle(x, keys, q, g, hi)
                    assert got[g, qi] == want, (dtype, dist, parts, g, q,
                                                got[g, qi], want)


_SHARDED_GRID_CODE = """
import os
os.environ["XLA_FLAGS"] = \\
    "--xla_force_host_platform_device_count={devices}"
import functools
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as PS
from repro.core.distributed import (gk_select_sharded,
                                    gk_select_multi_sharded,
                                    shard_map_compat)
from repro.kernels.ops import make_fused_multi_fn
from repro.launch.mesh import make_mesh
from _grid import (DTYPES, DISTRIBUTIONS, QS, make_case, needs_x64,
                   oracle_quantile)
P = {devices}
mesh = make_mesh((P,), ("data",))
n = P * 384


@functools.lru_cache(maxsize=None)
def engines():
    # Built once, jitted once per input dtype: every distribution cell
    # replays the same traces (cells share n), keeping the grid O(traces)
    # not O(cells).
    single = functools.partial(gk_select_sharded, q=0.5, eps=0.01,
                               axis="data", num_shards=P)
    multi = functools.partial(gk_select_multi_sharded, qs=QS, eps=0.01,
                              axis="data", num_shards=P,
                              fused_fn=make_fused_multi_fn())
    wrap = lambda body: jax.jit(shard_map_compat(
        body, mesh=mesh, in_specs=(PS("data"),), out_specs=PS()))
    return wrap(single), wrap(multi)


def run_cell(dtype, dist):
    x = make_case(dist, dtype, n, seed=5)
    jx = jnp.asarray(x)
    single, multi = engines()
    want_mid = oracle_quantile(x, 0.5)
    got = np.asarray(jax.device_get(single(jx)))
    assert got == want_mid, (dtype, dist, "single", got, want_mid)
    wants = [oracle_quantile(x, q) for q in QS]
    got_m = np.asarray(jax.device_get(multi(jx)))
    assert list(got_m) == wants, (dtype, dist, "multi", got_m, wants)


for dtype in DTYPES:
    if needs_x64(dtype):
        continue
    for dist in DISTRIBUTIONS:
        run_cell(dtype, dist)
jax.config.update("jax_enable_x64", True)
for dist in DISTRIBUTIONS:
    run_cell("float64", dist)
print("GRID-OK")
"""


class TestShardedEngines:
    """distributed_quantile's plans (single + fused multi) over real
    meshes: one subprocess per shard count runs the whole dtype x
    distribution grid (float64 cells after a global x64 switch), all
    shard counts in flight concurrently."""

    def test_sharded_grid_all_shard_counts(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(REPO, "src"), os.path.join(REPO, "tests")])
        env.pop("XLA_FLAGS", None)
        procs = {
            devices: subprocess.Popen(
                [sys.executable, "-c",
                 _SHARDED_GRID_CODE.format(devices=devices)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env)
            for devices in SHARD_COUNTS
        }
        failures = []
        for devices, proc in procs.items():
            try:
                out, err = proc.communicate(timeout=570)
            except subprocess.TimeoutExpired:
                proc.kill()
                out, err = proc.communicate()
                failures.append(f"P={devices}: timeout\n{err[-1500:]}")
                continue
            if proc.returncode != 0 or "GRID-OK" not in out:
                failures.append(f"P={devices}:\n{err[-2000:]}")
        assert not failures, "\n\n".join(failures)


class TestGridSelfConsistency:
    """The fixture module itself: oracle and rank rules must agree with the
    engine-side implementations they mirror."""

    def test_rank_rules_match_local_ops(self):
        from repro.core import local_ops
        for n in (1, 2, 9, 100, 3072, 65521):
            for q in (0.001, 0.1, 0.5, 0.75, 0.999, 1.0):
                assert target_rank(n, q) == local_ops.target_rank(n, q)
                from _grid import exact_target_rank
                assert (exact_target_rank(n, q)
                        == local_ops.exact_target_rank(n, q))
                assert (exact_target_rank(n, q)
                        == int(local_ops.target_rank_traced(
                            jnp.int32(n), q)))

    def test_oracle_is_partition_semantics(self):
        x = np.array([5.0, 1.0, 3.0, 3.0, 2.0], np.float32)
        assert oracle_kth(x, 1) == 1.0
        assert oracle_kth(x, 3) == 3.0
        assert oracle_kth(x, 5) == 5.0

    def test_every_distribution_materializes_every_dtype(self):
        for dtype, dist in _cells():
            x = make_case(dist, dtype, 384)
            assert x.size == 384
            assert not np.any(np.isnan(np.asarray(x, np.float64)))

"""Cluster-scale exact quantile job: the paper's headline experiment shape —
one flat dataset sharded across a device mesh, exact quantile in 3 collective
phases.  The mesh spans every device JAX sees: the chips of a TPU host, or
on CPU as many host devices as ``XLA_FLAGS`` asks for.

Run:  PYTHONPATH=src python examples/cluster_quantile.py
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
          PYTHONPATH=src python examples/cluster_quantile.py   # 8 on CPU
"""
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed_quantile, distributed_quantile_multi
from repro.launch.chip import enable_compile_cache
from repro.launch.mesh import make_mesh

enable_compile_cache()
ndev = len(jax.devices())
mesh = make_mesh((ndev,), ("data",))
rng = np.random.default_rng(0)
n = ndev * (1 << 20)
host = rng.uniform(-1e9, 1e9, size=n).astype(np.float32)
# one shard per device, placed directly (never the whole array on one chip)
x = jax.device_put(host, NamedSharding(mesh, P("data")))

for method in ["gk_select", "approx", "full_sort"]:
    t0 = time.perf_counter()
    v = distributed_quantile(x, 0.99, mesh, method=method)
    v.block_until_ready()
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    v = distributed_quantile(x, 0.99, mesh, method=method)
    v.block_until_ready()
    t_warm = time.perf_counter() - t0
    print(f"{method:10s} p99={float(v):.3f}  warm={t_warm*1e3:.1f} ms "
          f"(cold {t_cold*1e3:.0f} ms)")

flat = np.sort(host)
truth = flat[int(np.ceil(0.99 * n)) - 1]
exact = float(distributed_quantile(x, 0.99, mesh))
print(f"oracle p99={truth:.3f}  exact match: {exact == truth}")

# --- Q quantiles, ONE job: shared sketch, one count+extract phase, one
# butterfly for all Q candidate buffers (Spark runs Q separate jobs) --------
qs = (0.5, 0.9, 0.99, 0.999)
t0 = time.perf_counter()
vals = distributed_quantile_multi(x, qs, mesh)
vals.block_until_ready()
dt = time.perf_counter() - t0
wants = [flat[int(np.ceil(q * n)) - 1] for q in qs]
print(f"multi-quantile {qs} in one job ({dt*1e3:.0f} ms) on {ndev} devices: "
      f"{np.asarray(vals).round(3)}  exact: {list(np.asarray(vals)) == wants}")

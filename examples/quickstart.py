"""Quickstart: exact distributed quantiles with GK Select.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import (exact_quantile, gk_select, gk_select_multi,
                        approx_quantile, full_sort_quantile, GKSketch)
from repro.launch.chip import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)

# --- 1. exact quantile of a flat array (auto-partitioned) ------------------
x = rng.normal(size=1 << 20).astype(np.float32)
p99 = exact_quantile(jnp.asarray(x), 0.99, num_partitions=16)
print(f"exact p99      = {float(p99):.6f}")
print(f"numpy oracle   = {np.sort(x)[int(np.ceil(0.99 * x.size)) - 1]:.6f}")

# --- 2. partitioned data (one row per 'executor'), paper's 3-round algo ----
parts = jnp.asarray(x.reshape(16, -1))
median = gk_select(parts, 0.5, eps=0.01)                 # paper-faithful
median_fast = gk_select(parts, 0.5, eps=0.01, speculative=True)  # 2-round
# fused Pallas kernel: counts + both candidate bands in ONE HBM pass/shard
median_fused = gk_select(parts, 0.5, eps=0.01, block_select=True)
assert (float(median) == float(median_fast) == float(median_fused)
        == float(full_sort_quantile(parts, 0.5)))
print(f"median         = {float(median):.6f}  (3-round == 2-round == fused == sort)")

# --- 3. many quantiles in one job (shared sketch phase) ---------------------
qs = (0.01, 0.25, 0.5, 0.75, 0.99)
vals = gk_select_multi(parts, qs)
print("multi-quantile =", [f"{float(v):.4f}" for v in vals])

# --- 4. approximate-only path (Spark approxQuantile semantics) --------------
approx = approx_quantile(parts, 0.5, eps=0.01)
print(f"approx median  = {float(approx):.6f}  (rank error <= eps*n)")

# --- 5. the faithful streaming GK sketch (Spark QuantileSummaries) ----------
sk = GKSketch(eps=0.01)
sk.insert_batch(x)
print(f"GK sketch      : size={sk.size} tuples, query(0.5)={sk.query(0.5):.6f}")

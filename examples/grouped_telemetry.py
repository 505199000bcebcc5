"""Per-tenant telemetry quantiles: G groups x Q levels from ONE job.

The classic fleet-telemetry question — "p50 and p99 request latency for
EVERY tenant" — is a per-group quantile over a high-cardinality key.  The
per-group loop costs one full GK Select job per tenant; the grouped engine
(DESIGN.md §7) answers the whole (tenant, level) matrix in one job: one
segmented sketch (a single (key, value) sort per shard), one fused
count+extract pass per shard for ALL tenants' pivots, one butterfly, one
resolve.  Answers are EXACT — bit-identical to sorting each tenant's
latencies — including tenants with wildly different traffic volumes.

Run:  PYTHONPATH=src python examples/grouped_telemetry.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import gk_select_grouped, local_ops
from repro.kernels import ops as kernel_ops
from repro.launch import QuantileService
from repro.launch.chip import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)

# --- synthetic fleet: 12 tenants, heavy-tailed latencies, skewed traffic ----
TENANTS = 12
QS = (0.5, 0.99)
weights = rng.dirichlet(np.full(TENANTS, 0.5))       # skewed traffic shares
n = 12 * 8192
tenant = rng.choice(TENANTS, size=n, p=weights).astype(np.int32)
base = rng.lognormal(mean=1.0, sigma=0.6, size=n)
latency = (base * (1.0 + 0.3 * tenant)).astype(np.float32)   # per-tenant shift

# --- one grouped job over 12 pseudo-shards ----------------------------------
parts = 12
pv = jnp.asarray(latency).reshape(parts, -1)
pk = jnp.asarray(tenant).reshape(parts, -1)
kernel_ops.reset_hbm_passes()
matrix = np.asarray(gk_select_grouped(pv, pk, QS, num_groups=TENANTS,
                                      block_select=True))

print(f"{n} samples, {TENANTS} tenants, levels {QS} — one job")
print(f"{'tenant':>6} {'count':>7} {'p50 ms':>9} {'p99 ms':>9}")
for t in range(TENANTS):
    cnt = int((tenant == t).sum())
    print(f"{t:>6} {cnt:>7} {matrix[t, 0]:>9.3f} {matrix[t, 1]:>9.3f}")

# --- exactness: bit-identical to sorting each tenant's latencies ------------
for t in range(TENANTS):
    vals = np.sort(latency[tenant == t])
    for qi, q in enumerate(QS):
        k = local_ops.exact_target_rank(vals.size, q)
        assert matrix[t, qi] == vals[k - 1], (t, q)
print("\nevery cell bit-identical to the per-tenant sort oracle")

# --- the streaming face: ragged ingest, one fused HBM pass per chunk --------
# backend="pallas" pins the one-pass kernel contract; the CPU dispatch
# default (jnp) would honestly stream 3*G*Q passes per chunk instead
svc = QuantileService(eps=0.01, fused=True, backend="pallas")
for day in range(4):                      # e.g. four ingestion windows
    m = rng.integers(3000, 9000)
    t = rng.choice(TENANTS, size=m, p=weights).astype(np.int32)
    lat = (rng.lognormal(1.0, 0.6, size=m) * (1.0 + 0.3 * t)
           ).astype(np.float32)
    svc.ingest_grouped("latency", lat, t)

kernel_ops.reset_hbm_passes()
stream_matrix = np.asarray(svc.grouped("latency", QS, TENANTS))
print(f"\nstreamed {svc.grouped_stream_count('latency')} values in 4 ragged "
      f"chunks; grouped query cost {kernel_ops.hbm_passes()} fused HBM "
      f"passes (1 per chunk) for all {TENANTS}x{len(QS)} cells")
print(f"tenant 0 streamed p99 = {stream_matrix[0, 1]:.3f}")

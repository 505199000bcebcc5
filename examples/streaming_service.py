"""Streaming quantile service: persistent device-resident sketch state.

A stateless GK Select job pays its most expensive action — the sketch's
full per-shard sort — on EVERY query.  ``QuantileService`` maintains the
sketch incrementally as batches arrive, so exact queries run WARM: pivot
straight from the live sketch, then one count+extract pass — 2 of the
paper's 3 actions, zero sketch-phase sorts (DESIGN.md §6).

Run:  PYTHONPATH=src python examples/streaming_service.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import reset_sketch_sorts, sketch_sorts
from repro.launch import QuantileService
from repro.launch.chip import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)
svc = QuantileService(eps=0.01)

# --- a stream of per-step batches (e.g. activation magnitudes) --------------
batches = [rng.gamma(2.0, 1.5, size=8192).astype(np.float32) for _ in range(12)]
for b in batches:
    svc.ingest("activations", b)

everything = np.sort(np.concatenate(batches))
n = everything.size
print(f"ingested {n} values in {len(batches)} batches; "
      f"sketch rank bound = {svc.rank_bound('activations')} "
      f"(eps*n = {0.01 * n:.0f})")

# --- approximate queries: O(s) from the sketch alone, no data pass ----------
for q in (0.5, 0.99):
    approx = float(svc.approx("activations", q))
    k = max(1, int(np.ceil(q * n)))
    rank = np.searchsorted(everything, approx, side="right")
    print(f"approx q={q}: {approx:.4f}  (rank error {abs(rank - k)}, "
          f"bound {svc.rank_bound('activations')})")

# --- exact queries: WARM — no sketch-phase sort -----------------------------
for q in (0.5, 0.99, 0.999):
    k = max(1, int(np.ceil(q * n)))
    want = float(everything[k - 1])
    reset_sketch_sorts()
    warm = float(svc.exact("activations", q))           # 2 actions
    warm_sorts = sketch_sorts()
    reset_sketch_sorts()
    cold = float(svc.exact("activations", q, warm=False))   # 3 actions
    cold_sorts = sketch_sorts()
    assert warm == cold == want
    print(f"exact q={q}: {warm:.6f} == oracle; sketch sorts warm={warm_sorts} "
          f"cold={cold_sorts}")
assert warm_sorts == 0 and cold_sorts == len(batches)

# --- streams are independent ------------------------------------------------
svc.ingest("latencies", rng.lognormal(1.0, 0.6, size=4096).astype(np.float32))
print(f"p99 latency (exact, warm): "
      f"{float(svc.exact('latencies', 0.99)):.4f} over "
      f"{svc.stream_count('latencies')} samples; streams = {svc.streams()}")

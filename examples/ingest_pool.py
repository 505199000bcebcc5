"""Threaded ingest pipeline: worker buffers, fold scheduler, live queries.

``IngestPool`` runs N ingest workers over one ``QuantileService``: each
worker stages submitted batches host-side into a private buffer, and a
fold thread lands up to N full buffers per device dispatch — so producer
threads never block on device work, and the fixed per-dispatch overhead
is paid once per epoch batch instead of once per submitted batch
(DESIGN.md §10).  Queries run concurrently against the folded state;
``flush()`` is the barrier that makes them exact up to now, bit-identical
to a serial ingest of the same batches.

Run:  PYTHONPATH=src python examples/ingest_pool.py
      REPRO_INGEST_THREADS=8 PYTHONPATH=src python examples/ingest_pool.py
"""
import threading
import time

import numpy as np

from repro.launch import IngestPool, QuantileService, default_ingest_workers
from repro.launch.chip import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)
svc = QuantileService(eps=0.05, budget=128)
workers = max(1, default_ingest_workers())      # REPRO_INGEST_THREADS wins

# --- N producer threads, each submitting its own stream of batches ----------
streams = [f"tenant{i}" for i in range(4)]
plans = {name: [rng.gamma(2.0, 1.5, size=1024).astype(np.float32)
                for _ in range(24)] for name in streams}

with IngestPool(svc, workers=workers, epoch_values=4096) as pool:
    def producer(name):
        for batch in plans[name]:
            pool.submit(name, batch)            # queue handoff, no device work

    threads = [threading.Thread(target=producer, args=(n,)) for n in streams]
    for t in threads:
        t.start()

    # --- queries overlap ingest: readers never wait for producers -----------
    while any(t.is_alive() for t in threads) or pool.lag_values():
        try:
            p50 = float(svc.approx("tenant0", 0.5))
            print(f"  live: tenant0 p50~{p50:.3f} "
                  f"(staleness {pool.lag_values()} values)")
        except ValueError:
            pass                                # nothing folded yet
        time.sleep(0.005)
    for t in threads:
        t.join()

    # --- flush() barrier: exact-up-to-now, bit-identical to serial ingest ---
    pool.flush()
    stats = pool.stats()
    print(f"folded {stats['folded_values']} values in {stats['folds']:.0f} "
          f"folds ({stats['avg_buffers_per_fold']:.1f} buffers/fold, "
          f"max staleness {stats['max_lag_values']:.0f} values)")
    answers = svc.exact_all((0.5, 0.99))

serial = QuantileService(eps=0.05, budget=128)
for name in streams:
    for batch in plans[name]:
        serial.ingest(name, batch)
want = serial.exact_all((0.5, 0.99))
for name in streams:
    assert np.asarray(answers[name]).tobytes() == np.asarray(want[name]).tobytes()
    p50, p99 = (float(v) for v in answers[name])
    print(f"{name}: exact p50={p50:.4f} p99={p99:.4f} "
          f"over {svc.stream_count(name)} values == serial replay")
print(f"{workers} workers; exact answers are order-invariant, so any thread "
      f"schedule reproduces the serial result bit-for-bit")

"""Open loop against a live windowed percentile service.

One tick of every series is due every ``tick_period_s``: it lands through
``QuantileService.ingest_batch`` as one host array per series.  Queries
arrive at ``query_rate_per_s`` on a Poisson-like schedule and each asks
``windowed(series, q, window=Window(ticks=query_window_ticks))``.  One
thread serves the events in the order they fall due, and each is timed
from its due time to its answer, so a query that waits behind a tick or
another query carries that wait.

Set-up draws every tick the run needs on the device and copies it to the
host (where a service receives its observations), and fills the service
until its sub-window rows are first recycled, as they are in steady state
(``window_ticks`` plus one sub-window plus one ticks).  It asks
``WARM_QUERIES`` queries after each tick of the sub-window that follows
the first full window: queries there see every phase of the sub-window
cycle that the measured window will see, so that the programs the window
runs are loaded before it opens.

Every seed gets the same work in another order: the query gaps are the
same set of exponential quantiles, shuffled by the seed; series are
picked by Zipf rank, and every series holds the same number of values.

Config keys: ``series``, ``observations_per_tick``, ``window_ticks``,
``window_subs``, ``eps``, ``dtype``, ``log_median``, ``sigma``.
Traffic keys: ``tick_period_s``, ``query_rate_per_s``,
``query_window_ticks``, ``quantiles``, ``zipf_s``, ``drain_s``.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen, reference

# queries after each warm-up tick: a query's candidate cap, and with it the
# service's programs, depends on the phase of the sub-window cycle and on
# the series' own sketch, so set-up asks a few series at every phase
WARM_QUERIES = 8
DRAW_CHUNK_BYTES = 256 << 20   # ticks drawn per device call, at most 64


class Loop:
    def __init__(self, run):
        import repro.launch
        self.launch = repro.launch
        self.run = run
        cfg, tr = run.config, run.traffic
        if cfg["dtype"] != "float32":
            raise ValueError(f"the service cell draws float32 values, not "
                             f"{cfg['dtype']}")
        self.S, self.L = int(cfg["series"]), int(cfg["observations_per_tick"])
        self.window_ticks = int(cfg["window_ticks"])
        sub_ticks = -(-self.window_ticks // int(cfg["window_subs"]))
        self.fill = self.window_ticks + sub_ticks + 1
        self.query_window = int(tr["query_window_ticks"])
        self.qs = [float(q) for q in tr["quantiles"]]
        self.names = [f"series-{i:05d}" for i in range(self.S)]
        self.key = datagen.key(run.seed, "ticks")
        self.rng = np.random.default_rng([run.seed & (2**64 - 1), 2])
        self.ticks = {}                 # tick index -> (S, L) host values
        self.queries = []               # see window()
        self.tick_log = []              # (due_s, start_s, end_s)
        self.lateness = []
        self.ticks_due = 0

        self.svc = self.launch.QuantileService(
            eps=float(cfg["eps"]), window_ticks=self.window_ticks,
            window_subs=int(cfg["window_subs"]))
        for t in range(self.fill):
            self.svc.ingest_batch(self.names, list(self._tick(t)))
            if self.window_ticks <= t < self.window_ticks + sub_ticks:
                for i in range(t * WARM_QUERIES, (t + 1) * WARM_QUERIES):
                    jax.block_until_ready(self.svc.windowed(
                        self.names[i % self.S], self.qs[i % len(self.qs)],
                        window=self.launch.Window(ticks=self.query_window)))
            self.ticks.pop(t - self.query_window, None)
        # a tick's chip work runs on after ingest_batch returns: wait for
        # the last one here, so that set-up's work does not spill into the
        # window (the chip runs programs in the order they were launched)
        jax.block_until_ready(jnp.zeros(()) + 1)
        self.ingested = self.fill
        run.log(f"setup filled {self.fill} ticks of {self.S} series x "
                f"{self.L}; memory_stats {self.svc.memory_stats()}")

    def _tick(self, t: int) -> np.ndarray:
        """Tick ``t``'s values on the host, drawn in bulk on the device."""
        if t not in self.ticks:
            count = max(1, min(64, DRAW_CHUNK_BYTES
                               // (self.S * self.L * 4)))
            with self.run.span("datagen"):
                block = np.asarray(datagen.ticks(
                    self.key, jnp.arange(t, t + count), series=self.S,
                    per_tick=self.L, sigma=float(self.run.config["sigma"]),
                    log_median=tuple(self.run.config["log_median"])))
            for i in range(count):
                self.ticks[t + i] = block[i]
        return self.ticks[t]

    def _schedule(self, seconds: float):
        """(due_s, kind, detail) events of the window, in serving order."""
        period = float(self.run.traffic["tick_period_s"])
        events = [(i * period, 0, None)
                  for i in range(math.ceil(seconds / period))]
        rate = float(self.run.traffic["query_rate_per_s"])
        n = max(1, round(rate * seconds))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        self.rng.shuffle(gaps)
        due = np.cumsum(gaps) * seconds / (gaps.sum() + gaps.mean())
        ranks = np.arange(1, self.S + 1, dtype=np.float64)
        p = ranks ** -float(self.run.traffic["zipf_s"])
        by_rank = self.rng.permutation(self.S)
        series = by_rank[self.rng.choice(self.S, n, p=p / p.sum())]
        for i in range(n):
            events.append((float(due[i]), 1,
                           (int(series[i]), self.qs[i % len(self.qs)])))
        events.sort(key=lambda e: (e[0], e[1]))
        return events

    def window(self, seconds: float) -> None:
        """Serve the events due in ``seconds``, and those that fall behind
        for up to ``drain_s`` more.  Another call goes on from the tick
        this one reached."""
        events = self._schedule(seconds)
        first = self.ingested
        due_ticks = sum(kind == 0 for _, kind, _ in events)
        self.ticks_due += due_ticks
        batches = [list(self._tick(first + i)) for i in range(due_ticks)]
        window = self.launch.Window(ticks=self.query_window)
        give_up = seconds + float(self.run.traffic["drain_s"])
        t0 = time.perf_counter()
        for due, kind, detail in events:
            now = time.perf_counter() - t0
            if now < due:
                time.sleep(due - now)
            start = time.perf_counter() - t0
            if start > give_up:
                if kind == 1:
                    self.queries.append((due, detail, None, None, start))
                continue
            self.lateness.append(start - due)
            if kind == 0:
                with self.run.span("tick"):
                    self.svc.ingest_batch(self.names,
                                          batches[self.ingested - first])
                self.ingested += 1
                self.tick_log.append((due, start, time.perf_counter() - t0))
                continue
            s, q = detail
            answer = None
            with self.run.span("query"):
                try:
                    answer = np.asarray(self.svc.windowed(
                        self.names[s], q, window=window))
                except Exception as e:  # counted as failed, not fatal
                    self.run.log(f"query error {e!r}")
            self.queries.append((due, detail, self.ingested, answer,
                                 time.perf_counter() - t0))

    def results(self):
        lat = np.asarray([(end - due) * 1e3
                          for due, _, _, _, end in self.queries])
        failed = (sum(a is None for _, _, _, a, _ in self.queries)
                  + self.ticks_due - len(self.tick_log))
        ticks = np.asarray([(end - due) * 1e3
                            for due, _, end in self.tick_log])
        late = np.asarray(self.lateness) * 1e3
        log = self.run.log
        log(f"queries {len(self.queries)} ticks {len(self.tick_log)} of "
            f"{self.ticks_due} failed {failed}")
        if len(late):
            quarter = max(1, len(late) // 4)
            log(f"generator lateness ms p50 {np.percentile(late, 50):.3f} "
                f"p95 {np.percentile(late, 95):.3f} max {late.max():.3f} "
                f"mean first quarter {late[:quarter].mean():.3f} "
                f"last quarter {late[-quarter:].mean():.3f}")
        if len(ticks):
            log(f"tick latency ms p50 {np.percentile(ticks, 50):.3f} "
                f"max {ticks.max():.3f}")
        log(f"memory_stats {self.svc.memory_stats()}")
        metrics = {}
        if len(lat):
            metrics = {"query_p50_ms": float(np.percentile(lat, 50)),
                       "query_p95_ms": float(np.percentile(lat, 95))}
            log(f"query latency ms p50 {metrics['query_p50_ms']:.3f} p95 "
                f"{metrics['query_p95_ms']:.3f} p99 "
                f"{np.percentile(lat, 99):.3f} max {lat.max():.3f} "
                f"over {len(lat)} queries")
        return metrics, len(self.queries) + self.ticks_due, failed

    def release(self) -> None:
        self.svc = None

    def check(self) -> dict:
        """Every query's answer against the reference over the raw values
        of the ticks it could see: the ``query_window_ticks`` ticks
        ingested before it was served."""
        wrong = missing = 0
        for due, (s, q), ingested, answer, _ in self.queries:
            if answer is None:
                missing += 1
                continue
            seen = range(max(0, ingested - self.query_window), ingested)
            values = np.stack([self.ticks[t][s] for t in seen])
            expected = reference.quantile(values, q)
            if not reference.same(answer, expected):
                wrong += 1
                if wrong <= 5:
                    self.run.log(f"check query at {due:.3f}s series {s} "
                                 f"q {q}: answer {answer!r} reference "
                                 f"{expected!r} WRONG")
        self.run.log(f"checked {len(self.queries) - missing} queries, "
                     f"{wrong} wrong, {missing} unanswered")
        return {"wrong_answers": (wrong, 0), "missing_answers": (missing, 0),
                "nothing_checked": (int(len(self.queries) == missing), 0)}

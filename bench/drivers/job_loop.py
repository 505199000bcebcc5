"""Closed loop of one-shot quantile jobs: one job at a time, each over
fresh data.

Each job draws its (P, n_i) input on the device from (seed, job index),
after deleting the previous job's input, and then calls the entry point
(``repro.core.gk_select`` with its default arguments, NaN check included)
and waits for the answer.  A job is timed from that call to its answer;
drawing the next input is the only untimed work between jobs.  Fresh data
means no answer can be reused from one job to the next.

Config keys: ``partitions``, ``values_per_partition``, ``dtype``.
Traffic keys: ``distribution`` (a ``datagen.DISTRIBUTIONS`` name), ``q``,
``check_jobs`` (how many of the window's jobs the reference checks, drawn
from the seed).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen, reference

WARMUP = "warmup"


class Loop:
    def __init__(self, run):
        import repro.core
        from repro.core import lowering
        self.core = repro.core
        self.run = run
        cfg, traffic = run.config, run.traffic
        if cfg["dtype"] != "float32":
            raise ValueError(f"job_loop draws float32 data, not {cfg['dtype']}")
        self.shape = (int(cfg["partitions"]), int(cfg["values_per_partition"]))
        self.dist = traffic["distribution"]
        self.q = float(traffic["q"])
        self.key = datagen.key(run.seed, "job")
        self.jobs = []          # (index, seconds, answer | None, error | None)

        # warm-up without running a job: load the job's program (from the
        # compile cache after a checkout's first run), and run the NaN
        # check's eager ops, at the cell's shape on data no measured job sees
        x = self._draw(datagen.key(run.seed, WARMUP), 0)
        lowering.lower(self.core.gk_select, x, self.q).compile()
        jax.block_until_ready(jnp.any(jnp.isnan(x)))
        x.delete()

    def _draw(self, key, index):
        with self.run.span("datagen"):
            x = datagen.job_values(key, index, dist=self.dist,
                                   shape=self.shape)
            return jax.block_until_ready(x)

    def window(self, seconds: float) -> None:
        end = time.perf_counter() + seconds
        index = 0
        while time.perf_counter() < end:
            x = self._draw(self.key, index)
            answer = error = None
            with self.run.span("job"):
                start = time.perf_counter()
                try:
                    answer = jax.block_until_ready(
                        self.core.gk_select(x, self.q))
                except Exception as e:  # a failed job is counted, not fatal
                    error = repr(e)
                took = time.perf_counter() - start
            x.delete()
            self.jobs.append((index, took,
                              None if answer is None else np.asarray(answer),
                              error))
            index += 1

    def results(self):
        times = [took for _, took, _, _ in self.jobs]
        failed = sum(err is not None for _, _, _, err in self.jobs)
        log = self.run.log
        log(f"jobs {len(self.jobs)} failed {failed} job_seconds "
            + (f"{times}" if len(times) <= 20 else
               f"min {min(times)} median {np.median(times)} max {max(times)}"))
        for _, _, _, err in self.jobs:
            if err:
                log(f"job error {err}")
        return ({"job_s": float(np.mean(times))} if times else {},
                len(self.jobs), failed)

    def release(self) -> None:
        """Nothing of the system stays on the device between jobs."""

    def check(self) -> dict:
        log = self.run.log
        missing = sum(answer is None for _, _, answer, _ in self.jobs)
        done = [job for job in self.jobs if job[2] is not None]
        rng = np.random.default_rng([self.run.seed, 1])
        count = min(int(self.run.traffic["check_jobs"]), len(done))
        picked = sorted(rng.choice(len(done), count, replace=False))
        wrong = 0
        for i in picked:
            index, _, answer, _ = done[i]
            x = datagen.job_values(self.key, index, dist=self.dist,
                                   shape=self.shape)
            values = np.asarray(x)
            x.delete()
            expected = reference.quantile(values, self.q)
            del values
            ok = reference.same(answer, expected)
            wrong += not ok
            log(f"check job {index}: answer {answer!r} reference "
                f"{expected!r} {'ok' if ok else 'WRONG'}")
        return {"wrong_answers": (wrong, 0), "missing_answers": (missing, 0),
                "nothing_checked": (int(count == 0), 0)}

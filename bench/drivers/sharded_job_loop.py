"""Closed loop of one-shot quantile jobs over a column sharded across the
cell's chips: one job at a time, each over fresh data.

Each job draws its (P, n_i) input on the chips from (seed, job index): the
values ``datagen.job_values`` draws on one device, each chip drawing its
own P / chips partitions, laid end to end as the flat column the sharded
entry takes (``NamedSharding(mesh, P("data"))``), so that chip c holds
partitions c * P / chips onward and plays one group of executors.  The
draw is one program, and nothing of it crosses the host or a chip (but,
for ``sorted``, its few partition bounds).  The loop deletes
the previous job's input, draws the next, then calls the entry point
(``repro.core.distributed_quantile(x, q, mesh)`` with its default
arguments, NaN check included) and waits for the answer.  A job is timed
from that call to its answer; drawing the next input is the only untimed
work between jobs.

The check gathers a checked job's values to the host, from the same draw,
and compares the answer with the plain reference, as ``job_loop`` does.

Config keys: ``partitions`` (a multiple of the cell's chips),
``values_per_partition``, ``dtype``.  Traffic keys: ``distribution`` (a
``datagen.DISTRIBUTIONS`` name), ``q``, ``check_jobs`` (how many of the
window's jobs the reference checks, drawn from the seed).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench import datagen, reference
from bench.drivers import job_loop

AXIS = "data"


def draw_program(mesh: Mesh, dist: str, shape: tuple):
    """``(key, index) -> x``: job ``index``'s (P, n_i) values of
    ``datagen.job_values``, flat and sharded over ``mesh``, each chip
    drawing its own rows."""
    rows = NamedSharding(mesh, PartitionSpec(AXIS, None))

    def draw(k, index):
        x = datagen.DISTRIBUTIONS[dist](jax.random.fold_in(k, index), shape)
        return jax.lax.with_sharding_constraint(x, rows).reshape(-1)
    return jax.jit(draw, out_shardings=NamedSharding(mesh, PartitionSpec(AXIS)))


class Loop(job_loop.Loop):
    """``job_loop.Loop`` over the sharded entry; its results and release
    are the same."""

    def __init__(self, run):
        import repro.core
        from repro.core import lowering
        self.core = repro.core
        self.run = run
        cfg, traffic = run.config, run.traffic
        if cfg["dtype"] != "float32":
            raise ValueError(f"sharded_job_loop draws float32 data, not "
                             f"{cfg['dtype']}")
        self.shape = (int(cfg["partitions"]), int(cfg["values_per_partition"]))
        if self.shape[0] % len(run.devices):
            raise ValueError(f"{self.shape[0]} partitions do not split over "
                             f"{len(run.devices)} chips")
        self.mesh = Mesh(np.array(run.devices), (AXIS,))
        self.dist = traffic["distribution"]
        self.q = float(traffic["q"])
        self.key = datagen.key(run.seed, "job")
        self.draw = draw_program(self.mesh, self.dist, self.shape)
        self.jobs = []          # (index, seconds, answer | None, error | None)

        # warm-up without running a job, as job_loop's: the job's program
        # and the NaN check's ops, at the cell's shape and sharding, on data
        # no measured job sees
        x = self._draw(datagen.key(run.seed, job_loop.WARMUP), 0)
        lowering.lower(self.core.distributed_quantile, x, self.q,
                       self.mesh).compile()
        jax.block_until_ready(jnp.any(jnp.isnan(x)))
        x.delete()

    def _draw(self, key, index):
        with self.run.span("datagen"):
            return jax.block_until_ready(self.draw(key, index))

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
                        self.core.distributed_quantile(x, self.q, self.mesh))
                except Exception as e:  # a failed job is counted, not fatal
                    error = repr(e)
                took = time.perf_counter() - start
            x.delete()
            self.jobs.append((index, took,
                              None if answer is None else np.asarray(answer),
                              error))
            index += 1

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
            x = self.draw(self.key, index)
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

#!/usr/bin/env python3
"""End-to-end smoke run of GK Select and the quantile service on a TPU.

Drives the main path once through the entry points a user calls, at the
paper's data size, and checks every answer bit-exactly against numpy.
One process; all data is generated from ``--seed``.

Phases (one chip, the default):

  device   the accelerator JAX sees: platform, kind, count, memory_stats
  r1       the paper's one-shot job (arXiv 2511.12025: 10^9 values over 120
           partitions): 120 x 2^23 f32, 4.03 GB on the device, drawn
           uniform and zipf (``benchmarks.bench_distributions.make_dist``),
           one distribution at a time.  ``gk_select`` at q in {0.5, 0.99},
           3-round and speculative; ``gk_select_multi`` at
           (0.5, 0.9, 0.99, 0.999); ``gk_select(block_select=True)`` — the
           compiled fused Pallas kernel — at eps=1e-4 (at eps=0.01 the
           candidate cap is the whole partition, which no kernel holds in
           VMEM).  Reference: ``np.partition`` of the same array.
  service  ``QuantileService(eps=0.01)`` over 10^4 streams fed 8 ticks of
           1024 f32 values each through ``ingest_batch``: ``exact_all``,
           ``approx`` and ``exact``; a windowed service (``window_ticks=4``)
           answering ``windowed``; a ``fused=True`` service over 64 streams
           whose ``exact_all`` runs the compiled segmented kernel.
           Reference: a per-stream numpy sort.

``--chips 4`` runs only the sharded phase: ``distributed_quantile`` and
``distributed_quantile_multi`` on a 4-device ("data",) mesh over
4 x 2^28 f32 (one 1 GiB shard per chip, placed sharded from the start):
q=0.99 by ``method="gk_select"`` plain and ``fused=True`` against
``method="full_sort"`` and numpy, and (0.5, 0.9, 0.99, 0.999) from one
multi job, plain and fused.

Each phase first compiles its large programs in parallel threads
(``precompile``, through ``repro.core.lowering.lower``: the very program
the entry point then runs; about a minute each for v5e at the R1 size).
The first call of each then must not compile that program again.

Every kernel phase prints the ``LaunchPlan`` it ran and fails unless the
plan is a Pallas one (``pallas_tpu`` on the chip).  Lines starting
``smoke time`` are host wall times of this run (first call includes
compilation).  Without a TPU the script exits non-zero before any phase;
``--tiny`` (sizes only — the same paths run, kernels interpreted) is the
CPU rehearsal:

    python chip_smoke.py                # on a TPU host, one chip
    python chip_smoke.py --chips 4      # on a 4-chip TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --tiny --chips 4

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
import argparse
import collections
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]
# libtpu logs under /tmp unless told otherwise; this run writes only
# inside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

QS_R1 = (0.5, 0.99)
QS_MULTI = (0.5, 0.9, 0.99, 0.999)
QS_SERVICE = (0.5, 0.99)
BLOCK_EPS = 1e-4       # fused-kernel phases: the candidate cap fits VMEM


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# names of the programs XLA compiled (or fetched from the persistent
# cache), in order; appended from whichever thread compiles
COMPILED = []


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILED.append(kw.get("fun_name"))


def timed(label: str, fn, precompiled=frozenset()):
    """Call ``fn`` twice; print both wall times and what the first call
    compiled; return the second result.  The first call must not compile
    a program named in ``precompiled`` again."""
    import jax
    out = None
    for run in ("first", "warm"):
        COMPILED.clear()
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"smoke time {label} {run}: {time.perf_counter() - t0:.6f} s"
            f" ({len(COMPILED)} programs compiled)")
        again = [name for name in COMPILED if name in precompiled]
        check(not again, f"{label}: {again} compiled again after precompile")
    return out


def precompile(label: str, lowerings) -> frozenset:
    """Compile programs concurrently (XLA compiles off the GIL), so the
    calls that follow run them without compiling one after another.
    ``lowerings`` are thunks returning ``jax.stages.Lowered``
    (``lowering.lower`` of the later call).  Returns the compiled
    programs' names."""
    COMPILED.clear()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowerings)) as pool:
        list(pool.map(lambda lower: lower().compile(), lowerings))
    log(f"smoke time {label} compile of {len(lowerings)} programs in "
        f"parallel: {time.perf_counter() - t0:.6f} s")
    return frozenset(COMPILED)


def kth_values(flat: np.ndarray, ks) -> list:
    """The k-th smallest (1-based) values of ``flat`` for every k."""
    part = np.partition(flat, [k - 1 for k in ks])
    return [part[k - 1] for k in ks]


def expect_pallas_plans(label: str, plans, kernels) -> None:
    """Print the plans a kernel phase ran (``dispatch.record_plans``);
    fail on a missing or jnp one."""
    import jax
    want = "pallas_tpu" if jax.devices()[0].platform == "tpu" \
        else "pallas_interpret"
    for (kernel, plan), calls in collections.Counter(plans).items():
        log(f"plan {label}: {kernel} x{calls} {plan}")
    check(bool(plans), f"{label}: no kernel plan recorded")
    for kernel, plan in plans:
        check(kernel in kernels, f"{label}: unexpected kernel {kernel}")
        check(plan.backend.name == want,
              f"{label}: {kernel} ran {plan.backend.name}, want {want} "
              f"({plan.reason})")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(devices) -> None:
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    log(f"device memory_stats: {d.memory_stats()}")


def phase_r1(seed: int, P: int, n_i: int) -> None:
    from repro.kernels import dispatch
    # the zipf pass replays the programs the uniform pass traced, so the
    # plan recorded at that trace is the one both ran
    with dispatch.record_plans() as plans:
        pre = frozenset()
        for di, dist in enumerate(("uniform", "zipf")):
            pre = _r1_distribution(seed, P, n_i, di, dist, pre, plans)


def _r1_distribution(seed: int, P: int, n_i: int, di: int, dist: str,
                     pre: frozenset, plans) -> frozenset:
    """One distribution of the R1 job; compiles the programs first when
    ``pre`` (the names already compiled) is empty.  Returns the names it
    compiled."""
    import jax
    from benchmarks.bench_distributions import make_dist
    from repro.core import gk_select, gk_select_multi, local_ops, lowering

    n = P * n_i
    host = np.empty((P, n_i), np.float32)

    def fill(p):     # row by row: bounded temporaries, threads in C
        rng = np.random.default_rng([seed, di, p])
        host[p] = make_dist(dist, rng, 1, n_i)[0]

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(fill, range(P)))
    x = jax.device_put(host)
    log(f"r1 {dist}: {P} x {n_i} f32 = {n} values, "
        f"{x.nbytes / 1e9:.3f} GB on {x.devices()}")
    if not pre:     # the same shapes serve both distributions
        pre = precompile("r1", [
            *(lambda q=q, spec=spec: lowering.lower(
                gk_select, x, q, speculative=spec)
              for spec in (False, True) for q in QS_R1),
            lambda: lowering.lower(gk_select_multi, x, QS_MULTI),
            lambda: lowering.lower(gk_select, x, 0.99, eps=BLOCK_EPS,
                                   block_select=True, backend="pallas")])
    ks = {q: local_ops.target_rank(n, q) for q in QS_MULTI}
    want = dict(zip(ks, kth_values(host.ravel(), list(ks.values()))))

    for spec in (False, True):
        for q in QS_R1:
            got = timed(f"r1 {dist} gk_select q={q} speculative={spec}",
                        lambda: gk_select(x, q, speculative=spec), pre)
            check(np.asarray(got) == want[q],
                  f"r1 {dist} gk_select q={q} spec={spec}: "
                  f"{got} != {want[q]}")
            log(f"r1 {dist} gk_select q={q} speculative={spec}: "
                f"{float(got)!r} == numpy")
    got = timed(f"r1 {dist} gk_select_multi",
                lambda: gk_select_multi(x, QS_MULTI), pre)
    check(list(np.asarray(got)) == [want[q] for q in QS_MULTI],
          f"r1 {dist} gk_select_multi: {got}")
    log(f"r1 {dist} gk_select_multi {QS_MULTI}: "
        f"{[float(v) for v in got]} == numpy")
    got = timed(f"r1 {dist} gk_select block_select q=0.99",
                lambda: gk_select(x, 0.99, eps=BLOCK_EPS, block_select=True,
                                  backend="pallas"), pre)
    check(np.asarray(got) == want[0.99],
          f"r1 {dist} block_select: {got} != {want[0.99]}")
    log(f"r1 {dist} gk_select block_select q=0.99 eps={BLOCK_EPS}: "
        f"{float(got)!r} == numpy")
    expect_pallas_plans(f"r1 {dist} block_select", plans, {"fused_select"})
    if di == 0:
        block_select_default_eps(x, want[0.99])
    x.delete()
    return pre


def block_select_default_eps(x, want) -> None:
    """What a user's ``gk_select(block_select=True)`` at the default eps
    does: it runs the kernel and is exact, or, where the candidate cap
    outgrows VMEM (at the R1 size the cap is a whole partition), a compiled
    backend refuses with ``ValueError`` naming the footprint; it never
    runs jnp in the kernel's place."""
    from repro.core import gk_select
    from repro.kernels import dispatch
    with dispatch.record_plans() as plans:
        try:
            got = gk_select(x, 0.99, block_select=True, backend="pallas")
        except ValueError as e:
            check("VMEM budget" in str(e), f"block_select eps=0.01: {e}")
            log(f"r1 gk_select block_select q=0.99 eps=0.01: refused: {e}")
            return
    check(np.asarray(got) == want, f"block_select eps=0.01: {got} != {want}")
    log(f"r1 gk_select block_select q=0.99 eps=0.01: {float(got)!r} == numpy")
    expect_pallas_plans("r1 block_select eps=0.01", plans, {"fused_select"})


def _service_data(seed: int, S: int, ticks: int, L: int) -> np.ndarray:
    """(ticks, S, L) f32: even streams uniform, odd streams zipf."""
    from benchmarks.bench_distributions import make_dist
    rng = np.random.default_rng([seed, 7])
    data = np.empty((ticks, S, L), np.float32)
    for t in range(ticks):
        data[t, 0::2] = make_dist("uniform", rng, (S + 1) // 2, L)
        data[t, 1::2] = make_dist("zipf", rng, S // 2, L)
    return data


def phase_service(seed: int, S: int, ticks: int, L: int, S_fused: int,
                  window: int) -> None:
    from repro.core import local_ops
    from repro.kernels import dispatch
    from repro.launch import QuantileService, Window

    data = _service_data(seed, S, ticks, L)
    names = [f"s{i}" for i in range(S)]
    svc = QuantileService(eps=0.01)
    wsvc = QuantileService(eps=0.01, window_ticks=window)
    t0 = time.perf_counter()
    for t in range(ticks):
        rows = list(data[t])
        svc.ingest_batch(names, rows)
        wsvc.ingest_batch(names, rows)
    log(f"smoke time service ingest {ticks} ticks x {S} streams x {L} "
        f"(two services): {time.perf_counter() - t0:.6f} s")
    log(f"service: {S} streams, {ticks * S * L} values, "
        f"{ticks * S * L * 4 / 1e6:.1f} MB of ticks per service")

    per_stream = data.transpose(1, 0, 2).reshape(S, ticks * L)
    n_s = ticks * L
    ks = [local_ops.target_rank(n_s, q) for q in QS_SERVICE]
    part = np.partition(per_stream, [k - 1 for k in ks], axis=1)
    want = part[:, [k - 1 for k in ks]]                       # (S, Q)

    got = timed(f"service exact_all {QS_SERVICE}",
                lambda: svc.exact_all(QS_SERVICE))
    got = np.stack([np.asarray(got[nm]) for nm in names])
    check(np.array_equal(got, want), "service exact_all != numpy")
    log(f"service exact_all {QS_SERVICE}: {S} x {len(QS_SERVICE)} == numpy")

    probe = sorted({0, 1, S // 2, S - 1})
    for i in probe:
        nm, vals = names[i], per_stream[i]
        for qi, q in enumerate(QS_SERVICE):
            e = timed(f"service exact {nm} q={q}", lambda: svc.exact(nm, q))
            check(np.asarray(e) == want[i, qi],
                  f"service exact {nm} q={q}: {e} != {want[i, qi]}")
            a = float(svc.approx(nm, q))
            bound = svc.rank_bound(nm)
            lo, hi = int(np.sum(vals < a)) + 1, int(np.sum(vals <= a))
            check(hi >= lo and hi >= ks[qi] - bound and lo <= ks[qi] + bound,
                  f"service approx {nm} q={q}: rank [{lo}, {hi}] vs "
                  f"{ks[qi]} +- {bound}")
        log(f"service exact/approx {nm}: exact == numpy, approx within "
            f"its rank bound {bound}")

    recent = data[ticks - window:].transpose(1, 0, 2).reshape(S, -1)
    kw = [local_ops.target_rank(recent.shape[1], q) for q in QS_SERVICE]
    for i in probe:
        nm = names[i]
        for q, k in zip(QS_SERVICE, kw):
            w = timed(f"service windowed {nm} q={q}",
                      lambda: wsvc.windowed(nm, q, window=Window(ticks=window)))
            ref = np.partition(recent[i], k - 1)[k - 1]
            check(np.asarray(w) == ref,
                  f"service windowed {nm} q={q}: {w} != {ref}")
        log(f"service windowed {nm} last {window} ticks: == numpy")

    fsvc = QuantileService(eps=0.01, fused=True, backend="pallas")
    fnames = names[:S_fused]
    for t in range(ticks):
        fsvc.ingest_batch(fnames, list(data[t, :S_fused]))
    with dispatch.record_plans() as plans:
        got = timed(f"service fused exact_all {QS_SERVICE} "
                    f"({S_fused} streams)", lambda: fsvc.exact_all(QS_SERVICE))
    got = np.stack([np.asarray(got[nm]) for nm in fnames])
    check(np.array_equal(got, want[:S_fused]), "fused exact_all != numpy")
    log(f"service fused exact_all: {S_fused} x {len(QS_SERVICE)} == numpy")
    expect_pallas_plans("service fused exact_all", plans,
                        {"segmented_select"})


def phase_sharded(seed: int, n_local: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (distributed_quantile, distributed_quantile_multi,
                            local_ops, lowering)
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    mesh = make_mesh((4,), ("data",))
    n = 4 * n_local
    host = {}

    def shard(index):
        i = index[0].start // n_local
        if i not in host:
            rng = np.random.default_rng([seed, 11, i])
            host[i] = rng.uniform(-1e9, 1e9, size=n_local).astype(np.float32)
        return host[i]

    x = jax.make_array_from_callback((n,), NamedSharding(mesh, P("data")),
                                     shard)
    placed = {s.device for s in x.addressable_shards}
    check(len(x.addressable_shards) == 4 and len(placed) == 4,
          f"x is not one shard per device: {x.addressable_shards}")
    log(f"sharded: 4 x {n_local} f32 = {n} values, one "
        f"{n_local * 4 / 2**30:.3f} GiB shard on each of {sorted(map(str, placed))}")
    q = 0.99
    fused = dict(eps=BLOCK_EPS, fused=True, backend="pallas")
    runs = {
        "gk_select": (distributed_quantile, (x, q, mesh), {}),
        "gk_select fused": (distributed_quantile, (x, q, mesh), fused),
        "full_sort": (distributed_quantile, (x, q, mesh),
                      {"method": "full_sort"}),
        "multi": (distributed_quantile_multi, (x, QS_MULTI, mesh), {}),
        "multi fused": (distributed_quantile_multi, (x, QS_MULTI, mesh),
                        fused),
    }
    # the fused programs record their plans when traced, here
    with dispatch.record_plans() as plans:
        pre = precompile("sharded", [
            lambda entry=entry, args=args, kw=kw: lowering.lower(
                entry, *args, **kw)
            for entry, args, kw in runs.values()])
    flat = np.concatenate([host[i] for i in range(4)])
    ks = [local_ops.target_rank(n, q) for q in QS_MULTI]
    want = dict(zip(QS_MULTI, kth_values(flat, ks)))
    del flat

    for label, (entry, args, kw) in runs.items():
        got = timed(f"sharded {label}", lambda: entry(*args, **kw), pre)
        expect = [want[q]] if entry is distributed_quantile else \
            [want[level] for level in QS_MULTI]
        check(list(np.atleast_1d(np.asarray(got))) == expect,
              f"sharded {label}: {got} != {expect}")
        log(f"sharded {label} {args[1]}: "
            f"{np.atleast_1d(np.asarray(got)).tolist()} == numpy")
    expect_pallas_plans("sharded fused", plans,
                        {"fused_select", "fused_select_multi"})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal sizes; the same paths run")
    args = ap.parse_args()

    import jax
    from repro.launch.chip import (device_record, enable_compile_cache,
                                   require_accelerator)
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.tiny:
        devices = jax.devices()
    else:
        try:
            devices = require_accelerator("tpu")
        except RuntimeError as e:
            print(f"chip_smoke: {e}; refusing to run (--tiny rehearses "
                  f"on CPU)", file=sys.stderr)
            return 2
    phase_device(devices)
    if args.chips == 4:
        phase_sharded(args.seed, (1 << 12) if args.tiny else (1 << 28))
    else:
        if args.tiny:
            phase_r1(args.seed, P=6, n_i=1 << 14)
            phase_service(args.seed, S=64, ticks=8, L=128, S_fused=8,
                          window=4)
        else:
            phase_r1(args.seed, P=120, n_i=1 << 23)
            phase_service(args.seed, S=10_000, ticks=8, L=1024, S_fused=64,
                          window=4)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

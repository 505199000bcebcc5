"""Roofline benches: kernel bandwidth + legacy dry-run cells.

Section A — the dispatch layer's driver (docs/PERFORMANCE.md): every
kernel x backend pair reports measured us/call, modelled HBM traffic
(input bytes x the backend-honest pass count from ``ops.hbm_passes``),
achieved GB/s and the fraction of the device's peak bandwidth
(``launch.roofline.peak_hbm_bandwidth``, keyed by ``device_kind``; on a
device without a known peak the fraction is "not_measured"), plus the tile
config the plan chose.

The jnp-vs-dispatch wall-clock ratio for the fused kernel is always
recorded; it is ASSERTED > 1.0 only on a compiled Pallas backend (TPU/GPU).
On interpret-mode CPU CI the assert is replaced by the dispatch-correctness
contract: bit-parity of the Pallas path against the jnp oracle for all four
kernels, and the 1-vs-3 fused pass-count claim.

Section B — legacy: per-cell roofline terms from the dry-run JSON cache
(the table EXPERIMENTS.md §Roofline renders); no rows without the cache.
"""
import glob
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import dispatch, ops
from repro.launch import roofline

DRYRUN_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "dryrun")

G, Q = 4, 3   # segmented-select group/level matrix for the bench


def timed(fn, reps=3):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _data(rng, n):
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    keys = jnp.asarray(rng.integers(0, G, size=n).astype(np.int32))
    pivot = jnp.float32(np.median(np.asarray(x)))
    p1 = np.quantile(np.asarray(x), np.linspace(0.25, 0.75, Q))
    pivots_gq = jnp.asarray(np.tile(p1.astype(np.float32), (G, 1)))
    cap = int(np.ceil(0.01 * n)) + 2
    return x, keys, pivot, pivots_gq, cap


def _kernel_legs(x, keys, pivot, pivots_gq, cap, bk):
    """(name, ops-call for passes/us, dispatch-call for the plan)."""
    u = ops.to_sortable_u32(x)
    z = jnp.uint32(0)
    return [
        ("count3",
         lambda: ops.count3(x, pivot, backend=bk),
         lambda: dispatch.run_partition_count(x, pivot, backend=bk)),
        ("fused_select",
         lambda: ops.fused_count_extract(x, pivot, cap, backend=bk)[0],
         lambda: dispatch.run_fused_select(x, pivot, cap, backend=bk)),
        ("segmented_select",
         lambda: ops.segmented_count_extract(x, keys, pivots_gq, cap,
                                             backend=bk)[0],
         lambda: dispatch.run_segmented_select(x, keys, pivots_gq, cap,
                                               backend=bk)),
        ("byte_histogram",
         lambda: ops.byte_histogram(u, z, z, shift=24, backend=bk),
         lambda: dispatch.run_byte_histogram(u, z, z, 24, backend=bk)),
    ]


def _kernel_section(csv_rows, smoke):
    n_full = 2 ** 16 if smoke else 2 ** 20
    kind = jax.devices()[0].device_kind
    has_peak = kind in roofline.HBM_BW_BY_DEVICE_KIND
    rng = np.random.default_rng(0)

    default_bk = dispatch.resolve(None)
    legs = [default_bk]
    pallas_bk = dispatch.resolve("pallas")
    if pallas_bk.name != default_bk.name:
        legs.append(pallas_bk)

    for bk in legs:
        # interpret-mode Pallas is emulated compute: cap its n so the
        # smoke budget holds (its numbers are trends, never absolutes)
        n_eff = n_full if (bk.kind != "pallas" or bk.compiled) \
            else min(n_full, 2 ** 16)
        x, keys, pivot, pivots_gq, cap = _data(rng, n_eff)
        for name, op_call, run_call in _kernel_legs(
                x, keys, pivot, pivots_gq, cap, bk):
            ops.reset_hbm_passes()
            jax.block_until_ready(op_call())
            passes = ops.hbm_passes()
            _, p = run_call()
            us = timed(op_call)
            streams = 2 if name == "segmented_select" else 1
            bytes_moved = streams * n_eff * 4 * passes
            if has_peak:
                rl = roofline.kernel_roofline(bytes_moved, us * 1e-6, kind)
                peak = (f"peak={rl['peak_gbs']:.0f}GB/s "
                        f"frac={rl['frac_of_peak']:.4f}")
            else:
                peak = "peak=not_measured frac=not_measured"
            csv_rows.append((
                f"roofline/{name}/{bk.name}", f"{us:.0f}",
                f"passes={passes} "
                f"achieved={bytes_moved / (us * 1e-6) / 1e9:.2f}GB/s "
                f"{peak} n={n_eff} device={kind} "
                f"plan={p.backend.name} lanes={p.lanes} "
                f"block_rows={p.block_rows}"))

    # ---- wall-clock win: jitted jnp oracle vs the dispatch default --------
    x, keys, pivot, pivots_gq, cap = _data(rng, n_full)
    us_default = timed(
        lambda: ops.fused_count_extract(x, pivot, cap)[0])
    us_jnp = timed(
        lambda: ops.fused_count_extract(x, pivot, cap, backend="jnp")[0])
    ratio = us_jnp / max(us_default, 1e-9)
    asserted = default_bk.compiled and default_bk.kind == "pallas"
    if asserted:
        assert ratio > 1.0, (
            f"compiled {default_bk.name} fused kernel is not beating the "
            f"jnp oracle: {us_default:.0f}us vs {us_jnp:.0f}us")
    csv_rows.append(("roofline/win_fused_vs_jnp", f"{us_default:.0f}",
                     f"jnp={us_jnp:.0f}us ratio={ratio:.2f} "
                     f"backend={default_bk.name} asserted={asserted}"))

    # ---- dispatch correctness: the interpret-mode CI contract -------------
    xs, ks, pv, pg, cs = _data(rng, min(n_full, 2 ** 14))
    us_ = ops.to_sortable_u32(xs)
    z = jnp.uint32(0)
    pairs = [
        ("count3", lambda b: dispatch.run_partition_count(xs, pv,
                                                          backend=b)[0]),
        ("fused_select", lambda b: dispatch.run_fused_select(
            xs, pv, cs, backend=b)[0]),
        ("segmented_select", lambda b: dispatch.run_segmented_select(
            xs, ks, pg, cs, backend=b)[0]),
        ("byte_histogram", lambda b: dispatch.run_byte_histogram(
            us_, z, z, 24, backend=b)[0]),
    ]
    for name, call in pairs:
        got = jax.tree_util.tree_leaves(call("pallas"))
        want = jax.tree_util.tree_leaves(call("jnp"))
        for gg, ww in zip(got, want):
            assert np.array_equal(np.asarray(gg), np.asarray(ww)), \
                f"{name}: pallas/jnp mismatch"
    ops.reset_hbm_passes()
    jax.block_until_ready(
        ops.fused_count_extract(xs, pv, cs, backend="pallas")[0])
    p_pallas = ops.hbm_passes()
    ops.reset_hbm_passes()
    jax.block_until_ready(
        ops.fused_count_extract(xs, pv, cs, backend="jnp")[0])
    p_jnp = ops.hbm_passes()
    assert (p_pallas, p_jnp) == (1, 3), (p_pallas, p_jnp)
    csv_rows.append(("roofline/dispatch_parity", "0",
                     "kernels=4/4_bit_equal fused_passes=pallas:1,jnp:3"))


def _dryrun_section(csv_rows):
    cells = sorted(glob.glob(os.path.join(DRYRUN_DIR, "*__pod1.json")))
    if not cells:
        print("roofline: no dry-run cache (python -m repro.launch.dryrun "
              "writes it); section B skipped", file=sys.stderr)
        return
    for path in cells:
        r = json.load(open(path))
        tag = f"{r['arch']}/{r['shape']}"
        if r["status"] != "ok":
            csv_rows.append((f"roofline/{tag}", "0", r.get("reason",
                                                           r["status"])))
            continue
        t = r["roofline"]
        csv_rows.append((
            f"roofline/{tag}", f"{t['bound_s'] * 1e6:.0f}",
            f"dom={t['dominant']} compute={t['compute_s']:.3g}s "
            f"mem={t['memory_s']:.3g}s coll={t['collective_s']:.3g}s "
            f"useful={r['useful_flops_ratio']:.2f}"))


def run(csv_rows):
    smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    _kernel_section(csv_rows, smoke)
    if not smoke:
        _dryrun_section(csv_rows)
    return csv_rows

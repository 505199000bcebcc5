"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes two artifacts next to
this file with one unified stem: ``BENCH_results.csv`` (human diffable) and
``BENCH_results.json`` (machine-readable; schema in docs/PERFORMANCE.md —
name -> {us_per_call, derived}, plus a ``_meta`` record carrying platform /
default backend / jax version / smoke flag) so the perf trajectory is
tracked across PRs.

``REPRO_BENCH_SMOKE=1`` (or ``--smoke``) runs a ~30s subset on tiny sizes —
the CI configuration — and writes to ``*.smoke.*`` filenames so it never
clobbers the tracked full-run artifacts.  ``--only name[,name]`` (or
``REPRO_BENCH_ONLY``) filters to the named modules — CI uses it to run the
service dispatch-counter assertions as their own step."""
import csv
import io
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)                       # `benchmarks` package
sys.path.insert(0, os.path.join(_REPO, "src"))  # `repro` package

from benchmarks import (bench_scaling, bench_distributions, bench_complexity,
                        bench_rounds, bench_roofline, bench_fused,
                        bench_multi, bench_service, bench_grouped,
                        bench_windowed)

MODULES = [
    ("fig1_2_scaling", bench_scaling),
    ("fig3_4_distributions", bench_distributions),
    ("tab4_complexity", bench_complexity),
    ("tab5_rounds", bench_rounds),
    ("roofline", bench_roofline),
    ("fused", bench_fused),
    ("multi", bench_multi),
    ("service", bench_service),
    ("grouped", bench_grouped),
    ("windowed", bench_windowed),
]

# smoke: only the modules that honour REPRO_BENCH_SMOKE sizing and finish
# in seconds on CPU (the shard_map/HLO modules spawn 8-device subprocesses).
SMOKE_MODULES = [
    ("roofline", bench_roofline),
    ("fused", bench_fused),
    ("multi", bench_multi),
    ("service", bench_service),
    ("grouped", bench_grouped),
    ("windowed", bench_windowed),
]


def main() -> None:
    smoke = ("--smoke" in sys.argv[1:]
             or os.environ.get("REPRO_BENCH_SMOKE", "0") == "1")
    if smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    argv = sys.argv[1:]
    if "--only" in argv:
        only = argv[argv.index("--only") + 1]
    selected = SMOKE_MODULES if smoke else MODULES
    if only:
        wanted = {w.strip() for w in only.split(",") if w.strip()}
        pool = dict(MODULES)
        unknown = wanted - set(pool)
        if unknown:
            sys.exit(f"unknown bench module(s): {sorted(unknown)} "
                     f"(have {sorted(pool)})")
        selected = [(n, pool[n]) for n in sorted(wanted)]
    from repro.launch.chip import enable_compile_cache
    enable_compile_cache()
    rows = [("name", "us_per_call", "derived")]
    failed = False
    for name, mod in selected:
        print(f"== {name} ==", file=sys.stderr)
        try:
            mod.run(rows)
        except Exception as e:  # keep the harness running, fail at the end
            failed = True
            rows.append((f"{name}/ERROR", "0", f"{type(e).__name__}: {e}"))
    out = io.StringIO()
    w = csv.writer(out)
    for r in rows:
        w.writerow(r)
    text = out.getvalue()
    print(text)
    here = os.path.dirname(os.path.abspath(__file__))
    # Smoke runs write to *.smoke.* so they never clobber the tracked
    # full-run trajectory artifacts.
    suffix = ".smoke" if smoke else ""
    with open(os.path.join(here, f"BENCH_results{suffix}.csv"), "w") as f:
        f.write(text)

    def _num(us):
        try:
            return float(us)
        except ValueError:
            return us

    import jax
    from repro.kernels import dispatch
    from repro.launch.chip import device_record
    payload = {name: {"us_per_call": _num(us), "derived": derived}
               for name, us, derived in rows[1:]}
    payload["_meta"] = {
        "platform": jax.default_backend(),
        "device": device_record(),
        "default_backend": dispatch.select_backend().name,
        "jax": jax.__version__,
        "smoke": smoke,
    }
    with open(os.path.join(here, f"BENCH_results{suffix}.json"), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    if failed:
        # ERROR rows (e.g. a bench_fused parity assert) must fail CI.
        sys.exit(1)


if __name__ == "__main__":
    main()

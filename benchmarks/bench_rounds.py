"""Table V: communication & synchronization — structural round counts,
collective phases and network volume per algorithm, measured from compiled
HLO of the shard_map implementations (subprocess with 8 host devices).

The child only compiles and counts collectives, so it runs on the CPU
(``JAX_PLATFORMS=cpu``) and never competes for a chip its parent holds."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.distributed import (gk_select_sharded, count_discard_sharded,
                                    approx_quantile_sharded, full_sort_sharded,
                                    shard_map_compat)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
n = 8 * 65536
xs = jax.ShapeDtypeStruct((n,), jnp.float32)
out = {}

def phases(body):
    f = jax.jit(shard_map_compat(body, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P()))
    hlo = f.lower(xs).compile().as_text()
    a = hlo_analysis.analyze(hlo)
    return {"collective_ops": sum(a["collective_counts"].values()),
            "volume_bytes": a["collective_total_bytes"],
            "by_kind": a["collective_counts"],
            "has_while": " while(" in hlo}

out["gk_select"] = phases(functools.partial(
    gk_select_sharded, q=0.5, eps=0.01, axis="data", num_shards=8))
out["gk_select_spec"] = phases(functools.partial(
    gk_select_sharded, q=0.5, eps=0.01, axis="data", num_shards=8,
    speculative=True))
out["gk_select_gather"] = phases(functools.partial(
    gk_select_sharded, q=0.5, eps=0.01, axis="data", num_shards=8,
    reduce_strategy="all_gather"))
out["afs"] = phases(functools.partial(
    count_discard_sharded, q=0.5, axis="data", num_shards=8))
out["jeffers"] = phases(functools.partial(
    count_discard_sharded, q=0.5, axis="data", num_shards=8,
    collect_counts=True))
out["gk_sketch"] = phases(functools.partial(
    approx_quantile_sharded, q=0.5, eps=0.01, axis="data", num_shards=8))
out["full_sort"] = phases(functools.partial(
    full_sort_sharded, q=0.5, axis="data", num_shards=8))
print("JSON:" + json.dumps(out))
"""


def run(csv_rows):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(_SUB)],
                         capture_output=True, text=True, env=env, timeout=900)
    if res.returncode != 0:
        csv_rows.append(("tab5/ERROR", "0", res.stderr[-200:]))
        return csv_rows
    payload = [l for l in res.stdout.splitlines() if l.startswith("JSON:")][0]
    out = json.loads(payload[5:])
    n = 8 * 65536
    for algo, d in out.items():
        csv_rows.append((f"tab5/{algo}/collective_ops",
                         str(d["collective_ops"]),
                         f"while_loop={d['has_while']}"))
        csv_rows.append((f"tab5/{algo}/volume_bytes",
                         f"{d['volume_bytes']:.0f}",
                         f"bytes_per_elem={d['volume_bytes'] / n:.3f}"))
    return csv_rows

"""The paper's job as the four-chip cell runs it: ``distributed_quantile``
over a ("data",) mesh, its input drawn shard by shard, its spans and its
counts of collectives.  Multi-device runs go in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=<P>, as in
``test_distributed.py``; the main process keeps its one device."""
import pytest

from test_distributed import REPO, run_sub

# what the bodies below use besides run_sub's own preamble, indented as
# they are (run_sub dedents the body whole)
PREAMBLE = f"""
        import re, sys
        sys.path.insert(0, {REPO!r})
        from repro import obs
        from repro.core import lowering
"""


@pytest.mark.parametrize("dist", ["uniform", "zipf", "bimodal", "sorted"])
def test_sharded_draw_is_the_one_device_draw_shard_by_shard(dist):
    out = run_sub(PREAMBLE + f"""
        from jax.sharding import NamedSharding, PartitionSpec
        from bench import datagen
        from bench.drivers import sharded_job_loop
        shape = (8, 2048)
        program = sharded_job_loop.draw_program(mesh, {dist!r}, shape)
        k = datagen.key(2**31 + 77, "job")
        for index in (0, 5):
            x = program(k, index)
            assert x.shape == (8 * 2048,)
            assert x.sharding.is_equivalent_to(
                NamedSharding(mesh, PartitionSpec("data")), 1)
            one = np.asarray(datagen.job_values(k, index, dist={dist!r},
                                                shape=shape))
            assert np.array_equal(np.asarray(x), one.reshape(-1))
            # chip c holds partitions 2c and 2c + 1, end to end
            for shard in x.addressable_shards:
                c = mesh.devices.tolist().index(shard.device)
                assert np.array_equal(np.asarray(shard.data),
                                      one[2 * c: 2 * c + 2].reshape(-1))
        # nothing crosses a chip but, for "sorted", its few partition bounds
        hlo = program.lower(k, 0).compile().as_text()
        moved = re.findall(r"= (\S+) (?:all-gather|all-reduce|"
                           r"collective-permute|all-to-all)\(", hlo)
        assert moved == [] or {dist!r} == "sorted", moved
        sizes = [int(np.prod([int(d) for d in re.search(
            r"\[([\d,]*)\]", s).group(1).split(",") if d])) for s in moved]
        assert all(size <= 8 for size in sizes), moved
        print("DRAW-OK")
    """, devices=4)
    assert "DRAW-OK" in out


def test_bisection_under_shard_map_is_exact():
    """120 partitions over 4 devices with eps = 0.2: a cap of 196,610
    lanes, past ``BISECT_MIN_LANES``, so the resolve bisects inside
    ``shard_map``; ties included."""
    out = run_sub(PREAMBLE + """
        from repro.core import local_ops
        n_i = 8192
        n = 120 * n_i
        cap = local_ops.candidate_cap(n, 0.2, n // P)
        assert cap == 196_610 and cap >= local_ops.BISECT_MIN_LANES
        rng = np.random.default_rng(15)
        x = rng.normal(size=n).astype(np.float32)
        x[rng.integers(0, n, n // 8)] = np.float32(0.25)     # a run of ties
        jx = jnp.asarray(x)
        hlo = lowering.lower(distributed_quantile, jx, 0.5, mesh,
                             eps=0.2).compile().as_text()
        assert re.search(r'op_name="[^"]*phase_resolve/kth_bisect', hlo)
        for q in (0.01, 0.5, 0.99):
            k = int(np.ceil(q * n))
            want = np.partition(x, k - 1)[k - 1]
            got = np.asarray(distributed_quantile(jx, q, mesh, eps=0.2))
            assert got.dtype == np.float32 and got == want, (q, got, want)
        print("BISECT-OK")
    """, devices=4)
    assert "BISECT-OK" in out


@pytest.mark.parametrize("devices", [4, 6])
def test_collective_counts_are_those_of_the_program(devices):
    """``distributed.collectives`` equals the collectives the lowered
    program holds, and ``distributed.reduce_bytes`` the bytes its
    ppermutes carry (or its candidate all_gather hands to P - 1 chips),
    for every plan of the two entries; nothing is counted for a lowering
    or a plan outside GK Select."""
    out = run_sub(PREAMBLE + """
        from repro.core.engine import butterfly_steps
        n_local = 4096
        x = jnp.asarray(np.random.default_rng(3).normal(
            size=P * n_local).astype(np.float32))
        cap = int(np.ceil(0.01 * x.size)) + 2
        steps = len(butterfly_steps(P))
        assert steps == {4: 2, 6: 4}[P]
        cases = [
            (distributed_quantile, (0.9,), {}, 3 + steps, steps * cap * 4),
            (distributed_quantile, (0.9,), {"speculative": True},
             3 + 2 * steps, 2 * steps * cap * 4),
            (distributed_quantile, (0.9,), {"reduce_strategy": "all_gather"},
             4, (P - 1) * cap * 4),
            (distributed_quantile_multi, ((0.1, 0.9),), {},
             3 + 2 * steps, 2 * steps * 2 * cap * 4),
            (distributed_quantile_multi, ((0.1, 0.9),),
             {"pivots": [0.0, 1.0], "cap": 100}, 1 + 2 * steps,
             2 * steps * 2 * 100 * 4),
        ]
        ops = re.compile(r"stablehlo\\.(all_gather|all_reduce|"
                         r"collective_permute|all_to_all|reduce_scatter)\\b")
        permuted = re.compile(r'stablehlo\\.collective_permute"?\\(.*?'
                              r'-> tensor<([0-9x]+)xf32>')
        for entry, args, kw, collectives, nbytes in cases:
            text = lowering.lower(entry, x, *args, mesh, **kw).as_text()
            assert len(ops.findall(text)) == collectives, (entry, kw)
            carried = sum(4 * int(np.prod([int(d) for d in s.split("x")]))
                          for s in permuted.findall(text))
            if kw.get("reduce_strategy") != "all_gather":
                assert carried == nbytes, (entry, kw, carried, nbytes)
            obs.reset()
            entry(x, *args, mesh, **kw)
            assert obs.counters() == {"distributed.collectives": collectives,
                                      "distributed.reduce_bytes": nbytes}, kw
        obs.reset()
        lowering.lower(distributed_quantile, x, 0.9, mesh)
        distributed_quantile(x, 0.9, mesh, method="afs")
        assert obs.counters() == {}
        print("COUNTS-OK")
    """, devices=devices)
    assert "COUNTS-OK" in out


def test_sharded_entries_open_their_spans():
    out = run_sub(PREAMBLE + """
        import glob, tempfile
        from jax.profiler import ProfileData
        x = jnp.asarray(np.random.default_rng(5).normal(
            size=P * 1024).astype(np.float32))
        distributed_quantile(x, 0.99, mesh).block_until_ready()
        distributed_quantile_multi(x, (0.5, 0.99), mesh).block_until_ready()
        trace_dir = tempfile.mkdtemp()
        jax.profiler.start_trace(trace_dir)
        try:
            distributed_quantile(x, 0.99, mesh).block_until_ready()
            distributed_quantile_multi(x, (0.5, 0.99), mesh).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        spans = sorted(
            ((e.name[len(obs.PREFIX):], e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats), line.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events if e.name.startswith(obs.PREFIX)),
            key=lambda s: (s[1], -s[2]))

        def inside(parent):
            _, lo, hi, _, line = parent
            return [s for s in spans if s is not parent and s[4] == line
                    and lo <= s[1] and s[2] <= hi]

        for root_name in ("distributed_quantile", "distributed_quantile_multi"):
            [root] = [s for s in spans if s[0] == root_name]
            inner = inside(root)
            assert [s[0] for s in inner] == ["nan_check", "read", "dispatch"], \
                inner
            assert inner[0][3]["where"] == root_name
        print("SPANS-OK")
    """, devices=4)
    assert "SPANS-OK" in out

"""``repro.core.lowering``: an entry point lowered ahead of its call is the
program the call then runs."""
import numpy as np
import jax
import pytest

from repro.core import (distributed_quantile, distributed_quantile_multi,
                        gk_select, gk_select_multi, lowering)
from repro.launch.mesh import make_mesh

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compiled_names():
    names = []

    def listen(event, secs, **kw):
        if event == BACKEND_COMPILE:
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield names
    jax.monitoring.unregister_event_duration_listener(listen)


def _parts(seed=0):
    rng = np.random.default_rng(seed)
    return jax.device_put(rng.normal(size=(4, 4096)).astype(np.float32))


@pytest.mark.parametrize("entry,args,kw", [
    (gk_select, (0.37,), {"speculative": True, "eps": 0.02}),
    (gk_select_multi, ((0.25, 0.75),), {"eps": 0.02}),
])
def test_precompiled_entry_does_not_compile_again(entry, args, kw,
                                                  compiled_names):
    x = _parts()
    lowered = lowering.lower(entry, x, *args, **kw)
    assert isinstance(lowered, jax.stages.Lowered)
    lowered.compile()
    program = set(compiled_names)
    assert program
    compiled_names.clear()
    got = entry(x, *args, **kw)
    assert not program & set(compiled_names)
    flat = np.sort(np.asarray(x).ravel())
    qs = np.atleast_1d(args[0])
    want = [flat[int(np.ceil(q * flat.size)) - 1] for q in qs]
    np.testing.assert_array_equal(np.atleast_1d(np.asarray(got)), want)


def test_distributed_entries_lower():
    mesh = make_mesh((1,), ("data",))
    x = _parts().ravel()
    for entry, args in ((distributed_quantile, (0.5, mesh)),
                        (distributed_quantile_multi, ((0.5, 0.9), mesh))):
        assert isinstance(lowering.lower(entry, x, *args),
                          jax.stages.Lowered)


def test_lowering_skips_the_data_check():
    x = np.array(_parts())
    x[0, 0] = np.nan
    x = jax.device_put(x)
    with pytest.raises(ValueError, match="NaN"):
        gk_select(x, 0.5)
    assert isinstance(lowering.lower(gk_select, x, 0.5), jax.stages.Lowered)
    assert not lowering.active()


def test_entry_without_a_program_is_refused():
    with pytest.raises(TypeError, match="no program"):
        lowering.lower(lambda x: x + 1, 1)

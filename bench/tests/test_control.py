"""The control of ``correct``: the reference in the program's place,
computed in bfloat16, has to come out not correct in every cell."""
import json

import numpy as np
import pytest

from bench import control, reference
from conftest import JOB, SERVICE


def test_bf16_rounding_matches_a_bfloat16_cast():
    import ml_dtypes
    x = np.random.default_rng(0).lognormal(0, 8, 10_000).astype(np.float32)
    x[::2] *= -1
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(reference.round_bf16(x), want)
    assert (reference.round_bf16(x) != x).mean() > 0.9


@pytest.mark.parametrize("workload", [JOB, SERVICE])
def test_control_is_not_correct(tiny_root, capsys, workload):
    rc = control.main(["--workload", workload, "--seed", str(2**32 + 99),
                       "--seconds", "1", "--trace", "0"],
                      root=tiny_root, platform="cpu")
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0

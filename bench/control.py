#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, computed in bfloat16, the precision below the configurations'
float32.  A run of it must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py`` does, with the entry point that the
cell's driver calls replaced: ``repro.core.gk_select`` for ``job_loop``
cells, ``repro.launch.QuantileService.windowed`` for
``service_open_loop`` cells (the service still ingests, so the run's
load is the same).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import contextlib
import sys
from collections import deque
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _control_gk_select(base):
    """The bfloat16 reference in ``base``'s place.  Set-up still loads
    ``base``'s program, as it does in a run, so the load is the same."""
    import numpy as np
    from bench import reference
    from repro.core import lowering

    def gk_select(parts, q, **kwargs):
        if lowering.active():
            return base(parts, q, **kwargs)
        return np.asarray(reference.control_quantile(np.asarray(parts), q))
    return gk_select


def _control_service(base):
    """``base`` with ``windowed`` answered by the bfloat16 reference over
    the raw values it was given."""
    import numpy as np
    from bench import reference

    class ControlService(base):
        def __init__(self, *args, window_ticks, **kwargs):
            super().__init__(*args, window_ticks=window_ticks, **kwargs)
            self._raw = deque(maxlen=window_ticks)
            self._row = {}

        def ingest_batch(self, names, batches, **kwargs):
            super().ingest_batch(names, batches, **kwargs)
            if not self._row:
                self._row = {name: i for i, name in enumerate(names)}
            self._raw.append(np.stack([np.asarray(b) for b in batches]))

        def windowed(self, name, q, *, window):
            seen = list(self._raw)[-window.ticks:]
            values = np.stack([tick[self._row[name]] for tick in seen])
            return np.asarray(reference.control_quantile(values, q))

    return ControlService


@contextlib.contextmanager
def control(driver: str):
    """Put the bfloat16 reference in the place of what ``driver`` times."""
    import repro.core
    import repro.launch
    if driver == "job_loop":
        saved = (repro.core, "gk_select", repro.core.gk_select)
        repro.core.gk_select = _control_gk_select(saved[2])
    elif driver == "service_open_loop":
        saved = (repro.launch, "QuantileService", repro.launch.QuantileService)
        repro.launch.QuantileService = _control_service(saved[2])
    else:
        raise KeyError(f"no control for driver {driver!r}")
    try:
        yield
    finally:
        setattr(*saved)


def main(argv=None, *, root: Path = ROOT, platform: str = "tpu") -> int:
    for path in (str(root), str(root / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import run
    args = run.parse_args(argv)
    cell = run.load_cell(Path(root), args.workload)
    with control(cell.traffic["driver"]):
        return run.main(argv, root=root, platform=platform)


if __name__ == "__main__":
    sys.exit(main())

"""Helpers of the benchmark's own tests: everything at the tiny sizes of
the data files' `rehearsal` groups, on the CPU."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def manifest():
    from benchmark import harness

    return harness.load_manifest(ROOT)


@pytest.fixture
def make_run(manifest, tmp_path):
    """A harness.Run for a cell at rehearsal sizes, without the look for a
    chip."""
    from benchmark import harness

    def make(workload, seed=5, seconds=1.5, chips=None):
        cell, config, traffic = harness.resolve_cell(
            manifest, workload, root=ROOT, rehearse=True)
        harness.apply_environment(config)
        return harness.Run(
            manifest=manifest, cell=cell, config=config, traffic=traffic,
            seed=seed, seconds=seconds, trace=False,
            chips=chips or cell["chips"],
            peaks=harness.load_json(os.path.join(
                ROOT, "benchmark", "peaks.json"))["TPU v5 lite"],
            rehearse=True, out_dir=str(tmp_path), t0=time.monotonic(),
            compiles=None)

    return make

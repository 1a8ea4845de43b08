"""A run of the harness on the CPU, past its look for a chip, with the
timed path broken underneath: ``correct`` has to come out false once for
each fault a serving cell can have, and true with none."""
import pytest

from bench import harness
from bench.tests.conftest import tiny_run


@pytest.mark.parametrize("fault", [None, *harness.FAULTS])
def test_fault_makes_run_incorrect(fault):
    run, res = tiny_run(3, fault=fault)
    checks = res["checks"]
    assert checks["unserved"]["value"] == 0
    assert checks["wrong_tokens"]["value"] == 0
    assert res["readings"]["tokens"] >= 60
    assert harness.correct(checks) is (fault is None), checks

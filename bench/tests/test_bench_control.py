"""The control of each cell comes out not correct.

The control is the plain reference computed one precision step below what
the configuration states, put in the program's place (bfloat16 matrix
products for the tabular cells, whose configuration states float32 at
``high``; float8 for the LM cell, whose configuration states bfloat16). At
the cells' own sizes on the chip it is run by ``bench/control.py``; here it
runs at a CPU size, on three seeds, against the cells' limits."""
from __future__ import annotations

import pytest


@pytest.mark.parametrize("workload", ["linear-m8-fit", "linear-m8-serve",
                                      "stablelm-m2-fit"])
def test_control_fails_a_limit(small_cells, workload):
    from bench import control
    from bench.lib import harness
    spec = harness.load_spec()
    cell = harness.find_cell(spec, workload)
    cfg = harness.load_config(spec, cell["config"])
    kind = "serve" if "serve" in workload else "fit"
    limits = cfg["limits"][kind]
    lines = control.readings(workload, [5, 6, 7], 0.5, require_tpu=False)
    for line in lines:
        assert all(line["program"][k] <= v for k, v in limits.items()), line
        assert any(line["control"][k] > v for k, v in limits.items()), line


def test_fault_readings_fail_a_limit(small_cells):
    """``bench/control.py --faults`` plants each fault of the cell in turn;
    every fault's readings fail one of the cell's limits."""
    from bench import control
    from bench.lib import harness
    spec = harness.load_spec()
    cfg = harness.load_config(spec, "gal-linear-m8")
    limits = cfg["limits"]["fit"]
    lines = control.fault_readings("linear-m8-fit", [5], 0.5,
                                   require_tpu=False)
    assert len(lines) == 3
    for line in lines:
        assert any(line["readings"][k] > v for k, v in limits.items()), line

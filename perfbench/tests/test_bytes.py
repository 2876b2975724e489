"""The roofline's byte count is a function of the configuration and the
traffic alone: T and Cp read once, T written once, per device and
dispatch, however many steps the dispatch fuses."""

import pytest

from perfbench import harness


@pytest.mark.parametrize("workload,expected", [
    ("d3-512-open.steady", 1_610_612_736),          # 1.61 GB
    ("d3-256pc-periodic.mesh221", 3 * 64 * 2**20),  # 3 x 64 MiB
])
def test_compulsory_bytes(workload, expected):
    cell = harness.Cell(workload)
    got = cell.family.compulsory_bytes(cell.config, cell.traffic)
    assert got == expected
    more = dict(cell.traffic, n_inner=1000)
    assert cell.family.compulsory_bytes(cell.config, more) == expected

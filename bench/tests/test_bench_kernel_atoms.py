"""The reader of ``kernel_atom_share``: kernel atoms over all atoms a
window's scans evaluated, and nothing from a program without the counters
or from a window with no atoms."""

from types import SimpleNamespace

import pytest

from bench import registry


@pytest.mark.parametrize("name", ["kernel_atom_share.reports",
                                  "kernel_atom_share.clicks"])
@pytest.mark.parametrize("scan, want", [
    ({"kernel_atoms": 30, "host_atoms": 10}, 75.0),
    ({"kernel_atoms": 7, "host_atoms": 0}, 100.0),
    ({"kernel_atoms": 0, "host_atoms": 4}, 0.0),
    ({"kernel_atoms": 0, "host_atoms": 0}, None),
    ({"kernel_atoms": 5}, None),
    ({"scans": 12, "device_scans": 12}, None),
])
def test_kernel_atom_share_reads_the_window_counters(name, scan, want):
    got = registry.reader(name)(SimpleNamespace(scan=scan))
    assert got == (pytest.approx(want) if want is not None else None)

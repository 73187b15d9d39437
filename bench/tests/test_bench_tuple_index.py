"""The reader of ``tuple_index_share``: index groups over all tuple groups
of a window, and nothing from a program without the counters."""

from types import SimpleNamespace

import pytest

from bench import registry


@pytest.mark.parametrize("scan, want", [
    ({"tuple_index_groups": 99, "tuple_isin_groups": 1}, 99.0),
    ({"tuple_index_groups": 7, "tuple_isin_groups": 0}, 100.0),
    ({"tuple_index_groups": 0, "tuple_isin_groups": 4}, 0.0),
    ({"tuple_index_groups": 0, "tuple_isin_groups": 0}, None),
    ({"scans": 12}, None),
])
def test_tuple_index_share_reads_the_window_counters(scan, want):
    read = registry.reader("tuple_index_share.clicks")
    got = read(SimpleNamespace(scan=scan))
    assert got == (pytest.approx(want) if want is not None else None)

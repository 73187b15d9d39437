"""The reader of ``tuple_rest_cand_share``: tuple-group predicates whose
rest atoms ran on the candidates over all tuple-group predicates of a
window, and nothing from a program without the counters."""

from types import SimpleNamespace

import pytest

from bench import registry


@pytest.mark.parametrize("scan, want", [
    ({"tuple_rest_cand": 99, "tuple_rest_table": 1}, 99.0),
    ({"tuple_rest_cand": 7, "tuple_rest_table": 0}, 100.0),
    ({"tuple_rest_cand": 0, "tuple_rest_table": 4}, 0.0),
    ({"tuple_rest_cand": 0, "tuple_rest_table": 0}, None),
    ({"tuple_rest_cand": 3}, None),
    ({"tuple_index_groups": 12, "tuple_isin_groups": 0}, None),
])
@pytest.mark.parametrize("cell", ["clicks", "reports"])
def test_tuple_rest_cand_share_reads_the_window_counters(scan, want, cell):
    read = registry.reader(f"tuple_rest_cand_share.{cell}")
    got = read(SimpleNamespace(scan=scan))
    assert got == (pytest.approx(want) if want is not None else None)

"""The reader of ``tuple_fold_share``: tuple-group atoms folded into an
atom with the same left side over all tuple-group atoms probed in a
window, and nothing from a program without the counters."""

from types import SimpleNamespace

import pytest

from bench import registry


@pytest.mark.parametrize("scan, want", [
    ({"tuple_atoms": 4, "tuple_folded": 2}, 50.0),
    ({"tuple_atoms": 19, "tuple_folded": 10}, 100.0 * 10 / 19),
    ({"tuple_atoms": 8, "tuple_folded": 0}, 0.0),
    ({"tuple_atoms": 0, "tuple_folded": 0}, None),
    ({"tuple_atoms": 4}, None),
    ({"tuple_rest_cand": 3, "tuple_index_groups": 3}, None),
])
@pytest.mark.parametrize("cell", ["clicks", "reports"])
def test_tuple_fold_share_reads_the_window_counters(scan, want, cell):
    read = registry.reader(f"tuple_fold_share.{cell}")
    got = read(SimpleNamespace(scan=scan))
    assert got == (pytest.approx(want) if want is not None else None)

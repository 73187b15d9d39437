"""The benchmark's yardsticks: bytes needed, peaks, the registry, traffic."""

import json

import numpy as np
import pytest

from bench import peaks, registry, roofline
from bench import traffic as mixes

GE, LT, EQ = 5, 2, 0


def test_bytes_needed_counts_touched_columns_and_output_bits():
    # 3 columns, 2,500 rows = 3 blocks (1024, 1024, 452); nothing prunes
    slab = np.tile(np.arange(2500, dtype=np.int32), (3, 1))
    thr = np.array([[0], [0]], np.int32)  # K=2 bindings of "col0 >= 0"
    got = roofline.bytes_needed(slab, 2500, [(0, GE)], thr)
    assert got == 4 * 2500 + (2 * 2500 + 7) // 8


def test_bytes_needed_skips_blocks_no_binding_can_match():
    slab = np.arange(3000, dtype=np.int32)[None, :].repeat(2, axis=0)
    # binding 0 wants col0 in [0, 10), binding 1 col0 in [2100, 2110):
    # blocks 0 and 2 live, block 1 prunes; col 1 is not touched
    thr = np.array([[0, 10], [2100, 2110]], np.int32)
    got = roofline.bytes_needed(slab, 3000, [(0, GE), (0, LT)], thr)
    live_rows = 1024 + (3000 - 2048)
    assert got == 4 * live_rows + (2 * 3000 + 7) // 8


def test_bytes_needed_set_atoms_prune_by_keys_inside_block_bounds():
    slab = np.arange(2048, dtype=np.int32)[None, :].repeat(2, axis=0)
    thr = np.array([[np.iinfo(np.int32).min]], np.int32)  # tautology on col 0
    keys = np.array([5, 7], np.int32)  # only block 0 holds a key
    sets = ((1,), keys, np.zeros((1, 1), np.int32), np.full((1, 1), 2, np.int32))
    got = roofline.bytes_needed(slab, 2048, [(0, GE)], thr, sets)
    assert got == 2 * 4 * 1024 + (2048 + 7) // 8


def test_peaks_table_has_v5e_and_refuses_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_registry_finds_every_piece_of_the_committed_benchmark():
    sp = registry.spec()
    for cell in sp["workloads"]:
        cfg = registry.config(sp, cell["config"])
        assert cfg["name"] == cell["config"]
        assert registry.traffic(cell["traffic"])["loop"] in ("open", "closed")
        for kind in ("end_to_end", "per_layer"):
            assert registry.metrics_of(sp, kind, cell["name"])
    for m in sp["end_to_end"] + sp["per_layer"]:
        assert callable(registry.reader(m["name"]))


def test_registry_looks_pieces_up_by_file_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "c1.json").write_text(json.dumps({"name": "c1"}))
    (tmp_path / "traffic" / "mix1.json").write_text(json.dumps({"loop": "open"}))
    (tmp_path / "metrics" / "width.py").write_text(
        "def read(ctx):\n    return 3.0\n")
    (tmp_path / "metrics" / "width.exact.py").write_text(
        "def read(ctx):\n    return 4.0\n")
    sp = {"configs": [{"name": "c1", "file": "configs/c1.json"}],
          "workloads": [{"name": "w1", "config": "c1", "traffic": "mix1"}],
          "per_layer": [{"name": "width.mix1", "workloads": ["w1"]}],
          "end_to_end": [{"name": "setup_s"}]}
    assert registry.config(sp, "c1", root=tmp_path) == {"name": "c1"}
    assert registry.traffic("mix1", bench=tmp_path) == {"loop": "open"}
    assert registry.reader("width.mix1", bench=tmp_path)(None) == 3.0
    assert registry.reader("width.exact", bench=tmp_path)(None) == 4.0
    assert [m["name"] for m in registry.metrics_of(sp, "end_to_end", "w1")] == ["setup_s"]
    with pytest.raises(FileNotFoundError):
        registry.reader("absent.mix1", bench=tmp_path)
    with pytest.raises(KeyError):
        registry.workload(sp, "w2")


def test_a_configuration_without_a_reference_key_uses_tpch_lineage():
    ref = registry.reference({"pipelines": ["q3", "q21"]})
    assert ref.__file__ == str(registry.BENCH / "reference" / "tpch_lineage.py")
    assert set(ref.GROUP_KEYS) >= {"q3", "q21"} and callable(ref.build)
    sp = registry.spec()
    for cell in sp["workloads"]:
        cfg = registry.config(sp, cell["config"])
        assert set(cfg["pipelines"]) <= set(registry.reference(cfg).GROUP_KEYS)


def test_a_named_reference_resolves_to_its_file_and_an_unknown_one_raises(
        tmp_path):
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "mine.py").write_text(
        "GROUP_KEYS = {'p1': ('k',)}\n"
        "def build(data, query):\n    return query\n")
    ref = registry.reference({"reference": "mine", "pipelines": ["p1"]},
                             bench=tmp_path)
    assert ref.GROUP_KEYS == {"p1": ("k",)} and ref.build(None, "p1") == "p1"
    with pytest.raises(FileNotFoundError, match=str(tmp_path / "reference")):
        registry.reference({"reference": "absent"}, bench=tmp_path)
    with pytest.raises(KeyError, match="'mine'.*'p2'"):
        registry.reference({"reference": "mine", "pipelines": ["p1", "p2"]},
                           bench=tmp_path)


def test_an_unknown_pipeline_fails_setup_before_any_data(monkeypatch):
    import repro.tpch

    from bench import run

    sp = registry.spec()
    cfg = dict(registry.config(sp, sp["workloads"][0]["config"]),
               reference="tpch_reports")
    monkeypatch.setattr(registry, "config", lambda *a, **kw: cfg)

    def generate(*a, **kw):
        raise AssertionError("data generated before the reference was checked")

    monkeypatch.setattr(repro.tpch, "generate", generate)
    with pytest.raises(KeyError, match="'tpch_reports'.*'q3'"):
        run.run(["--workload", sp["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--rehearse", "--sf", "0.002"])


def test_open_schedule_gives_every_seed_the_same_sessions_in_another_order():
    mix = registry.traffic("debug")
    rows = {"a": 50, "b": 7, "c": 1000}
    s1 = mixes.open_schedule(mix, rows, 1, 60.0)
    s2 = mixes.open_schedule(mix, rows, 2 ** 40 + 3, 60.0)
    assert s1 != s2
    for s in (s1, s2):
        assert all(0 <= t < 60.0 for t, _, _ in s)
        assert all(0 <= r < rows[q] for _, q, r in s)
    # every seed asks the same (pipeline, row) multiset, all inside the window
    assert sorted((q, r) for _, q, r in s1) == sorted((q, r) for _, q, r in s2)
    n = round(mix["session_rate_per_s"] * (60.0 - mix["session_span_s"]))
    lo, hi = mix["session_rows"]
    assert abs(len(s1) - n * (lo + hi) / 2) <= hi * len(rows)
    # Zipf: the hottest row is asked most
    ask_a = [r for _, q, r in s1 if q == "a"]
    assert max(set(ask_a), key=ask_a.count) == 0


def test_closed_passes_ask_the_same_rows_with_pipelines_interleaved():
    mix = registry.traffic("clicks")
    rows = {"a": 50, "b": 7, "c": 1000, "none": 0}
    p0 = mixes.closed_pass(mix, rows, 2 ** 35 + 1, 0)
    p1 = mixes.closed_pass(mix, rows, 2 ** 35 + 1, 1)
    assert p0 != p1
    assert len(p0) == mix["sessions"]
    asked = [sorted((q, r) for q, rs in p for r in rs) for p in (p0, p1)]
    assert asked[0] == asked[1]
    assert sorted(asked[0]) == sorted(mixes.closed_rows(mix, rows, 2 ** 35 + 1))
    lo, hi = mix["session_rows"]
    assert all(q != "none" and lo <= len(rs) <= hi for q, rs in p0)
    # every prefix holds the pipelines in their (uniform) shares
    for k in (3, 10, len(p0) // 2):
        got = [q for q, _ in p0[:k]]
        assert max(got.count(q) for q in "abc") - min(
            got.count(q) for q in "abc") <= 1

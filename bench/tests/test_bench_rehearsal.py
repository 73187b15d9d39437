"""CPU rehearsal of the harness end to end: SF 0.002, interpret-mode
kernels, one short window per traffic mix; every answer must pass the
comparison, and a run that finds no TPU prints no result line."""

import pytest

from bench import registry, run

SEED = 2 ** 33 + 5  # wider than 32 bits, as the driver's seeds are


def rehearse(cell, *extra):
    return run.run(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                    "--rehearse", "--sf", "0.002", *extra])


def assert_all_correct(out):
    assert out["attempted"] > 0
    assert out["failed"] == 0
    assert out["checks"] and all(c["value"] == 0 for c in out["checks"].values())
    assert out["correct"] is True


# pairings that PERF.md keeps for later as data alone: rehearsed beside the
# committed cells, so that adding their BENCHMARK.json entries is enough
PENDING = {"sf1-debug": ("tpch-sf1", "debug", "sf1-clicks"),
           "sf1-store-debug": ("tpch-sf1-store", "debug", "sf1-clicks"),
           "sf1-reports": ("tpch-sf1-reports", "reports", "sf1-clicks")}

# the configuration and mix of a pending pairing whose files are not
# committed yet (PERF.md, Open questions): held here as they would be
IN_MEMORY_CONFIGS = {
    "tpch-sf1-reports": {
        "name": "tpch-sf1-reports", "scale_factor": 1,
        "pipelines": ["q1", "q4", "q6", "q12", "q14", "q15"],
        "store": False, "engine": {"backend": "pallas"},
        "service": {"max_batch": 32, "window_s": 0.003,
                    "cache_entries": 1024},
        "reference": "tpch_reports"},
}
IN_MEMORY_MIXES = {
    "reports": {"loop": "closed", "clients": 4, "page_rows": 1,
                "sessions": 16, "session_rows": [1, 1],
                "pipeline_weight": "output_rows", "row_zipf_a": 0,
                "warm_batches": [1, 2, 4], "check_sample": 400},
}


def spec_with(cell, monkeypatch):
    """The committed spec, plus ``cell`` if it is pending: its configuration
    entry, and its name beside its model cell's in every metric's list;
    a configuration or mix held in memory is served in place of its file."""
    sp = registry.spec()
    from_file = registry.config, registry.traffic
    monkeypatch.setattr(registry, "config", lambda sp, name, *a, **kw: (
        IN_MEMORY_CONFIGS.get(name) or from_file[0](sp, name, *a, **kw)))
    monkeypatch.setattr(registry, "traffic", lambda name, *a, **kw: (
        IN_MEMORY_MIXES.get(name) or from_file[1](name, *a, **kw)))
    if cell in PENDING:
        config, mix, like = PENDING[cell]
        sp["configs"].append({"name": config,
                              "file": f"bench/configs/{config}.json"})
        sp["workloads"].append({"name": cell, "config": config,
                                "traffic": mix, "chips": 1})
        for m in sp["end_to_end"] + sp["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    return sp


@pytest.mark.parametrize("cell", ["sf1-clicks", "sf1-debug",
                                  "sf1-store-debug", "sf1-reports"])
def test_every_cell_rehearses_correct(cell, monkeypatch):
    sp = spec_with(cell, monkeypatch)
    monkeypatch.setattr(registry, "spec", lambda *a, **kw: sp)
    out = rehearse(cell)
    assert_all_correct(out)
    assert set(out["metrics"]) == {
        m["name"] for m in registry.metrics_of(sp, "end_to_end", cell)}
    assert list(out)[-2:] == ["checks", "rehearsal"]


def test_closed_loop_mix_rehearses_correct(monkeypatch):
    # the committed closed loop, asked in pages of 32 rows (an audit's page)
    pages = dict(registry.traffic("clicks"), page_rows=32, session_rows=[8, 64])
    monkeypatch.setattr(registry, "traffic", lambda name: pages)
    assert_all_correct(rehearse("sf1-clicks"))


def test_no_tpu_prints_no_result_line(capsys):
    rc = run.main(["--workload", "sf1-clicks", "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err

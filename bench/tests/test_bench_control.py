"""The comparison fails what it must: the control (byte budget 0, so
superset answers) and faults planted under the timed path."""

import numpy as np

from bench import registry, run

SEED = 2 ** 32 + 11


def rehearse(*extra):
    out = run.run(["--workload", "sf1-clicks", "--seed", str(SEED),
                   "--seconds", "2", "--rehearse", "--sf", "0.002", *extra])
    assert out["attempted"] > 0
    return out


def test_control_superset_path_is_not_correct():
    out = rehearse("--control")
    assert out["correct"] is False
    assert out["checks"]["not_precise"]["value"] > 0


def test_a_flipped_kernel_mask_bit_is_not_correct(monkeypatch):
    from repro.core.scan import PallasBackend

    launch = PallasBackend._launch

    def flipped(self, *a, **kw):
        mask = np.array(launch(self, *a, **kw))
        mask[:, -1] = ~mask[:, -1]  # the last row of every scanned table
        return mask

    monkeypatch.setattr(PallasBackend, "_launch", flipped)
    out = rehearse()
    assert out["correct"] is False


def test_half_of_each_batch_left_out_is_not_correct(monkeypatch):
    from repro.core import PredTrace

    batch = PredTrace.query_batch

    def half(self, rows):
        k = (len(rows) + 1) // 2
        done = batch(self, rows[:k])
        return done + [done[i % k] for i in range(len(rows) - k)]

    # users enough that the service coalesces their clicks into batches
    busy = dict(registry.traffic("clicks"), clients=16)
    monkeypatch.setattr(registry, "traffic", lambda name: busy)
    monkeypatch.setattr(PredTrace, "query_batch", half)
    out = rehearse()
    assert out["correct"] is False
    assert out["checks"]["wrong_lineage"]["value"] > 0


def test_a_source_column_altered_in_place_is_not_correct(monkeypatch):
    from repro.core import PredTrace

    query = PredTrace.query
    done = []

    def altering(self, row):
        if not done:  # the program's input, reordered in place once
            col = self.catalog["lineitem"].cols["l_orderkey"]
            col[:] = col[::-1].copy()
            done.append(True)
        return query(self, row)

    monkeypatch.setattr(PredTrace, "query", altering)
    out = rehearse()
    assert out["correct"] is False
    assert out["checks"]["source_changed"]["value"] > 0

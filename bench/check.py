"""The comparison that decides a run's ``correct``.

Every number compared has the limit 0: lineage is exact, so one differing
row id, one answer flagged as a superset, or one request never answered
fails the run.

* ``missing``: requests of the window with no answer a minute after it
  closed, or that failed (over all requests, not the sample);
* ``wrong_output_rows``: pipelines in the sample whose output row count
  differs from the reference's;
* ``unknown_row``: sampled answers whose output row is not an output group
  of the reference;
* ``wrong_lineage``: sampled answers whose row ids differ from the
  reference's in any source table;
* ``not_precise``: sampled answers with a ``precise`` flag that is not True;
* ``source_changed``: source columns or vocabularies that differ, after
  the window, from the copy the reference reads, which was taken before
  any pipeline ran (the program must not alter its input in place).

The question a request asks is an output row index of the program's
pipeline; it is read back as that row's group-key values, and the
reference (the configuration's, ``registry.reference``) answers for the
group with those values.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Tuple

import numpy as np

LIMITS = {"missing": 0, "wrong_output_rows": 0, "unknown_row": 0,
          "wrong_lineage": 0, "not_precise": 0, "source_changed": 0}


def same_rows(got, want: np.ndarray) -> bool:
    g = np.asarray(got)
    if g.shape == want.shape and np.array_equal(g, want):
        return True
    return np.array_equal(np.unique(g.astype(np.int64)), want)


def compare(ref: ModuleType, data, outputs: Dict[str, List[Tuple]],
            sample: List[Tuple[str, int, object]], missing: int,
            source_changed: int = 0) -> Dict:
    """``ref`` is the reference module (``build(data, q)``); ``data`` the
    source copy (``{table: (cols, dicts)}``); ``outputs[q]`` the group-key
    tuple of each output row of the program's pipeline ``q`` (``()`` for
    each row of a global aggregate); ``sample`` is ``(pipeline, row,
    LineageAnswer)`` triples."""
    counts = dict.fromkeys(LIMITS, 0)
    counts["missing"] = missing
    counts["source_changed"] = source_changed
    empty = np.zeros(0, np.int64)
    refs = {}
    for q in sorted({q for q, _, _ in sample}):
        refs[q] = ref.build(data, q)
        counts["wrong_output_rows"] += int(len(outputs[q]) != len(refs[q]))
    for q, row, ans in sample:
        key = outputs[q][row]
        if key not in refs[q]:
            counts["unknown_row"] += 1
            continue
        want = refs[q].rows(key)
        got = ans.lineage
        if any(not same_rows(got.get(t, empty), want.get(t, empty))
               for t in set(want) | set(got)):
            counts["wrong_lineage"] += 1
        if not all(bool(v) for v in ans.precise.values()):
            counts["not_precise"] += 1
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in counts.items()}


def passed(checks: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

* configuration ``<name>`` -> the ``file`` its entry names
  (``bench/configs/<name>.json``);
* traffic mix ``<name>`` -> ``bench/traffic/<name>.json``;
* per-layer metric ``<name>`` -> ``bench/metrics/<name>.py``, else
  ``bench/metrics/<name up to its first dot>.py``, a module with
  ``read(ctx) -> float | None``;
* a configuration's plain reference -> ``bench/reference/<stem>.py`` for
  the configuration file's ``"reference": "<stem>"`` (``tpch_lineage``
  where the key is absent), a module with ``GROUP_KEYS: {pipeline: output
  group columns}`` and ``build(data, pipeline) -> RefQuery``.

A new configuration, mix or metric is a new file and a new entry; nothing
here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DEFAULT_REFERENCE = "tpch_lineage"


def spec(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(sp: Dict, name: str) -> Dict:
    for w in sp["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in sp['workloads']]}")


def config(sp: Dict, name: str, root: Path = ROOT) -> Dict:
    for c in sp["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench: Path = BENCH) -> Dict:
    with open(bench / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metrics_of(sp: Dict, kind: str, cell: str) -> List[Dict]:
    """``kind`` is ``end_to_end`` or ``per_layer``; entries without a
    ``workloads`` list apply to every cell."""
    return [m for m in sp[kind] if cell in m.get("workloads", [cell])]


def _load(path: Path, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH) -> Callable:
    for stem in (name, name.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.exists():
            return _load(path, "bench_metric_" + stem.replace(".", "_")).read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench / 'metrics'}")


def reference(cfg: Dict, bench: Path = BENCH) -> ModuleType:
    """The plain reference that the configuration ``cfg`` names; raises
    unless it knows the output group columns of every configured
    pipeline."""
    stem = cfg.get("reference", DEFAULT_REFERENCE)
    path = bench / "reference" / f"{stem}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reference {stem!r} under "
                                f"{bench / 'reference'}")
    mod = _load(path, "bench_reference_" + stem)
    absent = [q for q in cfg.get("pipelines", ()) if q not in mod.GROUP_KEYS]
    if absent:
        raise KeyError(f"reference {stem!r} ({path}) has no GROUP_KEYS for "
                       f"pipeline(s) {absent}")
    return mod

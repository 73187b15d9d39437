#!/usr/bin/env python
"""Execute every fenced ``python`` block in the docs — the CI docs gate.

Documentation code must run, not rot: this script extracts each fenced
```python block from ``docs/*.md`` and ``README.md``, executes the blocks of
each file in order in one shared namespace (so a later block may use an
earlier block's imports), and then runs ``examples/quickstart.py`` end to
end in the same process.  Everything runs at tier-1 scale — a failure means a doc example has
drifted from the real API.

  PYTHONPATH=src python scripts/run_doc_examples.py
  PYTHONPATH=src python scripts/run_doc_examples.py --skip-quickstart
"""

from __future__ import annotations

import argparse
import re
import runpy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

FENCE = re.compile(r"^```python\s*$(.*?)^```\s*$", re.M | re.S)


def blocks_of(path: Path):
    return [m.group(1) for m in FENCE.finditer(path.read_text())]


def run_file(path: Path) -> int:
    blocks = blocks_of(path)
    if not blocks:
        print(f"  {path.relative_to(ROOT)}: no python blocks")
        return 0
    ns: dict = {"__name__": f"doc_example_{path.stem}"}
    for i, src in enumerate(blocks, 1):
        t0 = time.perf_counter()
        try:
            exec(compile(src, f"{path.name}[block {i}]", "exec"), ns)
        except Exception:
            print(f"  {path.relative_to(ROOT)} block {i}/{len(blocks)}: FAILED",
                  file=sys.stderr)
            raise
        print(f"  {path.relative_to(ROOT)} block {i}/{len(blocks)}: ok "
              f"({time.perf_counter() - t0:.2f}s)")
    return len(blocks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--skip-quickstart", action="store_true",
                    help="only run the fenced doc blocks")
    args = ap.parse_args(argv)

    files = sorted((ROOT / "docs").glob("*.md")) + [ROOT / "README.md"]
    total = 0
    for path in files:
        total += run_file(path)
    print(f"[doc-examples] {total} fenced python blocks executed")

    if not args.skip_quickstart:
        # in this process: the doc blocks already hold the accelerator, and
        # a child process could not take it over
        t0 = time.perf_counter()
        runpy.run_path(str(ROOT / "examples" / "quickstart.py"),
                       run_name="__main__")
        print(f"[doc-examples] examples/quickstart.py ok "
              f"({time.perf_counter() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

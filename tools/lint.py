#!/usr/bin/env python
"""Repo lint gate: run the :mod:`repro.check.lint` JAX-pitfall rules
(dispatch-in-loop, vmap-over-scan, jit-needs-static, bench-schema)
over ``src/``, ``tools/``, and ``tests/``.

::

    python tools/lint.py            # lint the whole repo, exit 1 if dirty
    python tools/lint.py src/repro/fleet/search.py tools/obs_report.py

Pure stdlib -- importing the lint rules does not import JAX, so this
runs in CI before any accelerator setup.  Suppress a finding with a
``# lint: ok`` comment on the flagged line.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))

from repro.check.lint import lint_paths, lint_tree  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", type=Path,
                    help="files to lint (default: src/, tools/, tests/)")
    args = ap.parse_args(argv)
    if args.paths:
        findings = lint_paths(_ROOT, [p.resolve() for p in args.paths])
    else:
        findings = lint_tree(_ROOT)
    for f in findings:
        print(f)
    if findings:
        print(f"lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""The benchmark's own CPU tests: they import ``perfbench`` from the
checkout's root and ``repro`` from ``src``, whatever the working
directory."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def short_check(monkeypatch):
    """The tiny cells (``tiny.py``) serve requests of a few tokens: a run's
    check compares 24 served tokens here, not a chip run's 256."""
    from perfbench import check
    monkeypatch.setattr(check, "CHECK_TOKENS", 24)

"""Building one cell's system outside a benchmark run."""

from __future__ import annotations

import gc
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, run  # noqa: E402


def build(cell: str, seed: int, seconds: float, **kw):
    """A set-up system for ``cell`` under ``seed``, as a benchmark run
    has it (the chip checked, the compile cache on; ``kw`` as
    ``run.prepare`` takes it)."""
    manifest = kw.pop("manifest", None) or harness.Manifest()
    system, _ = run.prepare(manifest, cell, seed, seconds, **kw)
    system.setup()
    return system


def free(system) -> None:
    system.release()
    system.params = None
    gc.collect()

"""Checked-in kernel tuning table: load / lookup / save.

The autotuner (:mod:`repro.tune.autotune`) measures candidate tilings per
(kernel, fidelity mode, dtype, GEMM geometry) and writes the winners to a
JSON table.  The kernels consult :func:`lookup` whenever the caller leaves
the tiling unspecified, so a checked-in ``tuning_table.json`` next to this
module transparently accelerates every conv/matmul site without touching
call sites.

Only *bit-identical* tilings are legal table entries: a tiling may change
how fast a kernel runs, never what it returns.  The autotuner enforces
that at generation time and the kernels re-check the k-partition
defensively at lookup time (see ``repro.kernels.tiling``).

A table records the backend it was tuned on (``meta.backend``).  Its
timings say nothing about another backend, so :func:`lookup` ignores a
table tuned elsewhere: a CPU-tuned ``impl="direct"`` entry must not turn
the ``pallas_call`` kernels off on a TPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Iterator, Mapping

import jax

DIM_ORDERS = ("mnk", "kmn")
IMPLS = ("grid", "direct")

_DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "tuning_table.json")


@dataclasses.dataclass(frozen=True)
class Tiling:
    """One tuned kernel configuration.

    ``dim_order`` picks the grid iteration order: ``"mnk"`` keeps K
    innermost (the historical layout), ``"kmn"`` hoists K outermost.
    Either way each (i, j) output tile still visits its K blocks in
    ascending order, so accumulation order — and hence the bits — are
    unchanged.  ``impl`` selects the execution path: ``"grid"`` is the
    ``pallas_call`` kernel, ``"direct"`` is the plain-XLA lowering that
    replicates the same block decomposition (the fast path off-TPU,
    where ``pallas_call`` runs in interpret mode).
    """

    block_m: int
    block_n: int
    block_k: int
    dim_order: str = "mnk"
    impl: str = "grid"

    def __post_init__(self):
        if self.dim_order not in DIM_ORDERS:
            raise ValueError(f"dim_order must be one of {DIM_ORDERS}, "
                             f"got {self.dim_order!r}")
        if self.impl not in IMPLS:
            raise ValueError(f"impl must be one of {IMPLS}, "
                             f"got {self.impl!r}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Mapping) -> "Tiling":
        return cls(block_m=int(d["block_m"]), block_n=int(d["block_n"]),
                   block_k=int(d["block_k"]),
                   dim_order=str(d.get("dim_order", "mnk")),
                   impl=str(d.get("impl", "grid")))


def key(kernel: str, mode: str, dtype: str, m: int, k: int, n: int) -> str:
    """Canonical table key for one kernel geometry."""
    return f"{kernel}|{mode}|{dtype}|{m}x{k}x{n}"


# ---------------------------------------------------------------------------
# Table state.  ``_stack`` holds context overrides; the base table is loaded
# lazily from the checked-in JSON and cached.
# ---------------------------------------------------------------------------

_cache: dict | None = None
_cache_path: str | None = None
_cache_backend: str | None = None            # meta.backend of the cache
_stack: list[dict[str, Tiling] | None] = []   # None == lookups disabled


def load_table(path: str | None = None) -> dict[str, Tiling]:
    """Load (and cache) the tuning table.  Missing file -> empty table."""
    global _cache, _cache_path, _cache_backend
    p = path or _DEFAULT_PATH
    if _cache is not None and _cache_path == p:
        return _cache
    entries: dict[str, Tiling] = {}
    backend = None
    if os.path.exists(p):
        with open(p) as f:
            raw = json.load(f)
        for k, v in raw.get("entries", {}).items():
            entries[k] = Tiling.from_json(v)
        backend = raw.get("meta", {}).get("backend")
    _cache, _cache_path, _cache_backend = entries, p, backend
    return entries


def save_table(entries: Mapping[str, Tiling], path: str,
               meta: Mapping | None = None) -> None:
    """Write a tuning table as deterministic (sorted-key) JSON."""
    doc = {"meta": dict(meta or {}),
           "entries": {k: entries[k].to_json() for k in sorted(entries)}}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def invalidate_cache() -> None:
    global _cache, _cache_path, _cache_backend
    _cache, _cache_path, _cache_backend = None, None, None


def lookup(kernel: str, mode: str, dtype: str,
           m: int, k: int, n: int) -> Tiling | None:
    """Look up a tuned tiling; ``None`` means use the kernel default.

    Context overrides apply as given.  The checked-in table applies only
    on the backend it was tuned on (its ``meta.backend``)."""
    if _stack:
        top = _stack[-1]
        if top is None:          # disabled() context
            return None
        return top.get(key(kernel, mode, dtype, m, k, n))
    entries = load_table()
    if _cache_backend != jax.default_backend():
        return None
    return entries.get(key(kernel, mode, dtype, m, k, n))


@contextlib.contextmanager
def overrides(entries: Mapping[str, Tiling]) -> Iterator[None]:
    """Replace the active table with ``entries`` inside the context."""
    _stack.append(dict(entries))
    try:
        yield
    finally:
        _stack.pop()


@contextlib.contextmanager
def disabled() -> Iterator[None]:
    """Force kernel-default tilings inside the context."""
    _stack.append(None)
    try:
        yield
    finally:
        _stack.pop()

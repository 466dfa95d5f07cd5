"""The one traffic generator: it reads a mix's JSON file and the seed.

A mix is data (``traffic/<name>.json``).  Keys this generator reads:

  loop               "closed" (each client waits for its reply) or
                     "open" (arrivals on the wall clock)
  clients            closed loop: number of clients
  rate_per_s         open loop: mean arrival rate
  arrivals           open loop: {"dist": "gamma", "cv": c} (bursty; c=1
                     is Poisson) or {"dist": "fixed"}
  prompt_len         {"dist": "loguniform"|"uniform"|"lognormal"|"fixed",
  output_len          "lo", "hi", "median", "sigma", "value"}: lengths in
                     tokens, clipped to [lo, hi]
  frames_per_request images per request (CNN), from a pool of
  frame_pool         distinct seeded frames held on the host
  size_pool          closed loop: how many request sizes to draw
  base_seed          seed of the sizes and gaps (default 0)
  check_requests     how many finished requests the check compares

Every run seed gets the same request sizes and the same arrival times,
in the same order, drawn from ``base_seed``: the seed draws only the
content of prompts and frames (and the system's weights).  For a queue
the order is part of the work: with the sizes and gaps permuted by the
seed, the p95 time to first token of the chat mix spread by a third
from seed to seed (PERF.md), so two seeds now offer the same work.
"""

from __future__ import annotations

import math

import numpy as np


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 63))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from one length spec."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if dist == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif dist == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), size=n))
    elif dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def length_range(spec: dict) -> tuple[int, int]:
    """The least and the greatest length a spec can draw."""
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["lo"]), int(spec["hi"])


class Traffic:
    """One mix under one seed."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self._base = int(spec.get("base_seed", 0))

    def rng(self, stream: int) -> np.random.Generator:
        """A generator for one use of the run seed (content, sampling)."""
        return np.random.default_rng([self.seed % (1 << 63), stream])

    # -- language-model requests ------------------------------------------
    def _sizes(self, n: int) -> list[tuple[int, int]]:
        base = _rng(self._base)
        prompt = draw_lengths(self.spec["prompt_len"], n, base)
        out = draw_lengths(self.spec["output_len"], n, base)
        return [(int(p), int(o)) for p, o in zip(prompt, out)]

    def closed_requests(self) -> list[tuple[int, int]]:
        """(prompt_len, output_len) in the order clients take them."""
        return self._sizes(int(self.spec.get("size_pool", 4096)))

    def open_schedule(self, seconds: float) -> list[tuple[float, int, int]]:
        """(due_s, prompt_len, output_len) for every arrival in
        ``[0, seconds)``: ``round(rate * seconds)`` arrivals whose gaps
        are scaled so the last is due at ``seconds * (n - 1) / n``."""
        n = max(1, round(self.spec["rate_per_s"] * seconds))
        arr = self.spec.get("arrivals", {"dist": "gamma", "cv": 1.0})
        base = _rng(self._base + 1)
        if arr["dist"] == "gamma":
            shape = 1.0 / arr["cv"] ** 2
            gaps = base.gamma(shape, 1.0, size=n)
        elif arr["dist"] == "fixed":
            gaps = np.ones(n)
        else:
            raise ValueError(f"unknown arrival distribution {arr['dist']!r}")
        due = np.cumsum(gaps) - gaps[0]     # the first arrives at 0
        due *= seconds * (n - 1) / n / max(due[-1], 1e-12) if n > 1 else 0.0
        return [(float(t), p, o) for t, (p, o) in zip(due, self._sizes(n))]

    def prompt(self, length: int, vocab: int,
               rng: np.random.Generator) -> np.ndarray:
        return rng.integers(0, vocab, size=length).astype(np.int32)

    # -- images -------------------------------------------------------------
    def frames(self, size: int, channels: int = 3) -> np.ndarray:
        """The pool of distinct frames, float32 in [0, 1), on the host."""
        n = int(self.spec["frame_pool"])
        return self.rng(3).random((n, size, size, channels),
                                  dtype=np.float32)

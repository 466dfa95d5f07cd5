"""The front door: ``serve.load(model_id)`` -> a server with submit().

One entry point covers both serve surfaces:

  * LM configs get :class:`LMServer` — the continuous batcher behind a
    synchronous ``submit``/``drain`` pair plus an async ``generate``
    coroutine (concurrent callers share the batch; the decode loop is
    pumped cooperatively, one tick per waiter round).
  * CNN configs get :class:`CNNServer` — forward-only micro-batching:
    submitted images ride one fixed-geometry jit'd forward in pool-sized
    chunks (one compile, any request count).

Both are views over the SAME resident cell per model id (the registry
compiles at most once per process): serving more users never re-stages
the ROM trunk.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api, cnn
from repro.serve import registry, trace
from repro.serve.pool import (PagedPool, SlotPool, suggest_paged,
                              suggest_slots)
from repro.serve.scheduler import ContinuousBatcher


class LMServer:
    """Continuous-batching decode serving for one resident LM cell.

    The KV pool is PAGED by default for families that support it
    (``paged=None`` -> ``api.supports_paging``): requests share one
    block pool through per-request block tables instead of each pinning
    a full-horizon cache row, so mixed-length traffic packs more
    concurrent requests into the same plan-budgeted bytes.  Pass
    ``paged=False`` for the dense :class:`~repro.serve.pool.SlotPool`,
    or ``paged=True`` to demand paging (raises for families that cannot
    page, e.g. SWA rings / ssm state).  ``n_blocks``/``block_size``
    size the paged pool (defaults: dense-equivalent capacity in
    ``max_len // 8``-position blocks); ``prefill_chunk`` is forwarded
    to the batcher (chunked prefill admission).

    With a :class:`~repro.scenario.ScenarioStore` attached, one cell
    serves N scenarios: ``swap_scenario`` (or ``submit(...,
    scenario=...)``) queues a branch hot-swap behind the in-flight
    requests — zero trunk recompile, no trunk traffic (one read of the
    ROM ``C`` and ``U`` derives the served branch again), and every
    request decodes entirely under the scenario it was admitted with.

    ``spec_k > 0`` turns on speculative decode (the YOLoC-native
    draft/verify split — see ``serve.scheduler``): up to ``spec_k``
    tokens per row drafted by the branch-only model (ROM trunks
    skipped), then one batched full-cell ``verify_step`` per round.
    Output stays bit-identical to ``spec_k=0`` greedy decode.
    ``draft_source`` optionally replaces the branch drafter with a
    callable (benchmarks use it to dial acceptance rates).
    """

    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 dtype=jnp.float32, store=None, scenario=None,
                 paged: bool | None = None, n_blocks: int | None = None,
                 block_size: int | None = None,
                 prefill_chunk: int | None = None, spec_k: int = 0,
                 draft_source=None):
        self.model = model
        self.store = store
        if paged is None:
            paged = api.supports_paging(model.cfg)
        elif paged and not api.supports_paging(model.cfg):
            raise ValueError(
                f"paged=True but {model.cfg.name!r} (family "
                f"{model.cfg.family!r}, sliding_window="
                f"{model.cfg.sliding_window}) cannot page its KV cache; "
                f"pass paged=False for a dense SlotPool")
        if paged:
            if block_size is None:
                block_size = min(64, max(8, max_len // 8))
                while max_len % block_size:
                    block_size -= 1
            if n_blocks is None:
                # dense-equivalent byte budget: n_slots full horizons
                n_blocks = n_slots * (max_len // block_size)
            self.pool = PagedPool(model, n_slots, n_blocks, block_size,
                                  max_len, dtype=dtype)
        else:
            self.pool = SlotPool(model, n_slots, max_len, dtype=dtype)
        self.batcher = ContinuousBatcher(model, params, self.pool,
                                         scenario=scenario,
                                         prefill_chunk=prefill_chunk,
                                         spec_k=spec_k,
                                         draft_source=draft_source)

    @property
    def params(self):
        """The served params tree every device call reads: the source
        tree plus each site's branch in the activation dtype
        (``rebranch.serving_branch``).  The batcher owns it: scenario
        swaps donate the old tree, so this is the ONE valid reference."""
        return self.batcher.params

    @property
    def scenario(self):
        return self.batcher.scenario

    def swap_scenario(self, name: str):
        """Queue a hot-swap to a registered scenario's branch (applies
        at a decode-step boundary after in-flight requests retire)."""
        if self.store is None:
            raise ValueError(
                "no ScenarioStore attached to this server; serve.load"
                "(model_id, scenario=...) or pass store= to LMServer")
        self.batcher.swap(name, self.store.get(name))

    # -- sync surface ---------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, eos_id=None,
               scenario=None):
        if scenario is not None and \
                scenario != self.batcher.pending_scenario():
            self.swap_scenario(scenario)
        return self.batcher.submit(prompt, max_new_tokens, eos_id=eos_id,
                                   scenario=scenario)

    def step(self) -> bool:
        return self.batcher.step()

    def drain(self, max_steps: int | None = None) -> int:
        return self.batcher.drain(max_steps)

    # -- async surface --------------------------------------------------
    async def generate(self, prompt, max_new_tokens: int,
                       eos_id=None, scenario=None) -> list[int]:
        """Submit and await one request; concurrent callers batch.

        Cooperative pump: each waiter advances the shared scheduler one
        tick per event-loop round, so N concurrent ``generate`` calls
        decode as one batch instead of N solo loops.
        """
        req = self.submit(prompt, max_new_tokens, eos_id=eos_id,
                          scenario=scenario)
        while not req.done:
            self.batcher.step()
            await asyncio.sleep(0)
        return list(req.tokens)


class CNNServer:
    """Forward-only serving for CNN configs: one jit'd fixed-batch cell.

    Requests are padded into ``n_slots``-row chunks so every call hits
    the same compiled executable; pad rows are sliced off the output
    (inference BN uses frozen statistics, so rows are independent and
    padding never changes a real row's result).
    """

    def __init__(self, model, params, *, n_slots: int, store=None,
                 scenario=None):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self.model = model
        self.params = params
        self.store = store
        self.scenario = scenario
        self.n_slots = int(n_slots)
        self._forward = jax.jit(model.forward)

    def swap_scenario(self, name: str):
        """Hot-swap to a registered scenario's branch.  Forward serving
        is synchronous, so the swap applies immediately (there are no
        in-flight requests to protect); the jitted forward is reused —
        no recompile, no trunk traffic."""
        if self.store is None:
            raise ValueError(
                "no ScenarioStore attached to this server; serve.load"
                "(model_id, scenario=...) or pass store= to CNNServer")
        from repro.scenario import swap_params
        self.params = swap_params(self.params, self.store.get(name))
        self.scenario = name

    def submit(self, images) -> np.ndarray:
        """images: [B, H, W, C] -> model outputs for all B rows."""
        with trace.span("cnn.request"):
            with trace.span("cnn.copy_in"):
                images = jnp.asarray(images)
                if images.ndim == 3:
                    images = images[None]
            trace.annotate(frames=images.shape[0])
            outs = []
            for lo in range(0, images.shape[0], self.n_slots):
                with trace.span("cnn.copy_in"):
                    chunk = images[lo:lo + self.n_slots]
                    pad = self.n_slots - chunk.shape[0]
                    if pad:
                        chunk = jnp.concatenate(
                            [chunk, jnp.zeros((pad, *chunk.shape[1:]),
                                              chunk.dtype)], 0)
                with trace.span("cnn.forward"):
                    out = self._forward(self.params, chunk)
                with trace.span("cnn.copy_out"):
                    outs.append(np.asarray(out[:self.n_slots - pad]
                                           if pad else out))
            return np.concatenate(outs, 0)

    async def generate(self, image) -> np.ndarray:
        """Async single-image front door (symmetry with LMServer)."""
        await asyncio.sleep(0)
        return self.submit(image[None] if np.asarray(image).ndim == 3
                           else image)[0]


def load(model_id: str, *, params=None, key=None, n_slots=None,
         max_len: int = 128, dtype=jnp.float32,
         sram_capacity_bytes: int = 64 << 20, scenario: str | None = None,
         paged: bool | None = None, n_blocks: int | None = None,
         block_size: int | None = None, prefill_chunk: int | None = None,
         spec_k: int = 0, draft_source=None):
    """One front door for LM decode and CNN forward serving.

    Resolves ``model_id`` through the registry (the cell is compiled at
    most once per process), initialises params unless given, and sizes
    the KV pool from the entry's placement plan when ``n_slots`` is not
    forced: dense pools via :func:`~repro.serve.pool.suggest_slots`,
    paged pools via :func:`~repro.serve.pool.suggest_paged` (same byte
    budget, roughly 2x the rows — short requests only pin the blocks
    they fill).  ``paged``/``n_blocks``/``block_size``/``prefill_chunk``
    /``spec_k``/``draft_source`` are forwarded to :class:`LMServer`
    (ignored for CNN configs, which have no KV state and do not decode).

    scenario: start the server on a registered scenario's branch (see
    ``registry.scenario_store`` / ``repro.scenario``): the branch is
    implanted over the resident trunk before serving, and the returned
    server carries the store so ``swap_scenario`` / ``submit(...,
    scenario=...)`` can hot-swap to the other registered scenarios.
    """
    model, plan = registry.compile_entry(model_id)
    if params is None:
        params = model.init(key if key is not None
                            else jax.random.PRNGKey(0))
    store = registry.scenario_store(model_id) \
        if scenario is not None or registry.has_scenarios(model_id) \
        else None
    if scenario is not None:
        from repro.scenario import swap_params
        params = swap_params(params, store.get(scenario))
    if isinstance(model.cfg, cnn.CNNConfig):
        return CNNServer(model, params, n_slots=n_slots or 8,
                         store=store, scenario=scenario)
    if paged is None:
        paged = api.supports_paging(model.cfg)
    if n_slots is None:
        if paged:
            n_slots, nb, bs = suggest_paged(
                model, plan, max_len, dtype=dtype,
                sram_capacity_bytes=sram_capacity_bytes,
                block_size=block_size)
            n_blocks = n_blocks if n_blocks is not None else nb
            block_size = bs
        else:
            n_slots = suggest_slots(
                model, plan, max_len, dtype=dtype,
                sram_capacity_bytes=sram_capacity_bytes)
    return LMServer(model, params, n_slots=n_slots, max_len=max_len,
                    dtype=dtype, store=store, scenario=scenario,
                    paged=paged, n_blocks=n_blocks, block_size=block_size,
                    prefill_chunk=prefill_chunk, spec_k=spec_k,
                    draft_source=draft_source)

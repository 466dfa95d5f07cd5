"""Serving runtime: continuous batching over one resident ROM cell.

The paper's premise is that ROM-CiM weights never move — so a compiled
cell should amortize across as many concurrent users as the scheduler
can feed it.  This package owns requests on top of
``repro.deploy.compile_model``:

  * :mod:`repro.serve.registry`  — model-id -> (config, plan, engine,
    tune) entries, compiled lazily into ONE resident
    :class:`~repro.deploy.CompiledModel` per id (the exo
    ``model_base_shards`` shape: ids are data, deployment is a lookup),
    with an optional LRU residency cap (``set_max_resident``) evicting
    the least-recently-used cell through the same ``evict`` path.
  * :mod:`repro.serve.pool`      — KV-cache pools sized from the
    :class:`~repro.plan.PlacementPlan`'s SRAM residency stats (weights
    already resident in SRAM shrink the activation/KV budget): the
    dense per-request ``SlotPool`` and the ``PagedPool``, which carves
    the same byte budget into fixed-size blocks shared through
    per-request block tables (short requests stop paying full-horizon
    bytes).
  * :mod:`repro.serve.scheduler` — admission queue + continuous-batching
    scheduler: solo prefills (whole-prompt or chunked, interleaved with
    decode steps) join the batch at decode-step boundaries, finished
    requests retire without draining the batch, and every request's
    output is bit-identical to a solo prefill+decode run.  With
    ``spec_k > 0`` the scheduler decodes speculatively: the ReBranch
    branch (``trunk_skip`` draft config, same params tree) proposes k
    tokens per row, one batched ``verify_step`` through the full cell
    checks them, and rejected tails roll back in the pool — greedy
    output stays bit-identical to plain decode.
  * :mod:`repro.serve.server`    — the async front door shared by LM
    decode serving and ``cnn.CNNConfig`` forward-only serving:
    ``serve.load(model_id)`` returns a server with ``submit``.
  * :mod:`repro.serve.trace`     — host spans of the serving path
    (admission, prefill, decode dispatch, sampling, copies), kept in
    memory while ``trace.enable()`` is on; off by default.

Scenario multiplexing (``repro.scenario``): one resident cell serves N
registered scenarios.  ``registry.scenario_store(model_id)`` holds the
named branches (LRU device cache over host/checkpoint sources) and
``serve.load(model_id, scenario=...)`` / ``LMServer.swap_scenario``
hot-swap the SRAM branch over the fixed ROM trunk at decode-step
boundaries — zero trunk recompile, no trunk traffic (an LM swap reads
each site's ROM ``C`` and ``U`` once to derive its served branch),
in-flight requests finish on the scenario they were admitted under.
"""

from repro.serve.pool import (PagedPool, SlotPool,        # noqa: F401
                              suggest_paged, suggest_slots)
from repro.serve.registry import (ModelEntry, compile_entry,  # noqa: F401
                                  evict, has_scenarios, max_resident,
                                  register, registered_ids, resident_ids,
                                  resolve, scenario_store,
                                  set_max_resident)
from repro.serve.scheduler import ContinuousBatcher, Request  # noqa: F401
from repro.serve.server import CNNServer, LMServer, load  # noqa: F401

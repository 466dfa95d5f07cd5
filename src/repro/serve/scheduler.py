"""Continuous batching: interleave prefill and decode over one cell.

The scheduler owns requests.  Life of a request:

  submit -> admission queue (FIFO) -> [pool.try_admit: a row, and —
  paged — blocks for the whole request] solo prefill (batch=1,
  bit-identical to the standalone path; prompts longer than
  ``prefill_chunk`` run one chunk per tick, interleaved with decode) ->
  KV adopted into the pool (dense row copy or paged block scatter) ->
  joins the batched ``decode_step`` at the next step boundary ->
  retires when done (max_new_tokens or EOS) -> capacity freed, the
  rest of the batch keeps decoding.

Invariants (tested in tests/test_serve.py):
  * occupancy never exceeds the pool size;
  * admission is FIFO and work-conserving — a request waits only while
    the pool cannot guarantee it (rows, or paged block reservations)
    and admits as soon as it can (no starvation);
  * chunked prefill never stalls the batch: in-flight decodes advance
    on every tick a prefill chunk runs;
  * each request's tokens are bit-identical to a solo
    ``prefill`` + ``decode_step`` run of the same prompt, because the
    per-row attention cache (dense rows, or paged blocks gathered
    through the block table) makes batched decode row-independent.

Decoding is greedy (argmax) — deterministic, which is what makes the
bit-parity invariant testable end to end.

Speculative decode (``spec_k > 0``): the YOLoC-native draft/verify
split.  Each round, a cheap DRAFT model — the SRAM ReBranch branch with
the ROM trunk skipped (``CompiledModel.draft_decode_step``), or an
injected ``draft_source`` — proposes up to k tokens per row; then ONE
batched ``verify_step`` over the [N, k] block runs the full trunk+branch
cell and greedy accept-longest-prefix keeps the drafted prefix that
matches the verify argmaxes, plus the first mismatch's correction for
free.  Accepted output is bit-identical to non-speculative greedy decode
regardless of draft quality: position i's verify logits are computed
from the same accepted tokens plain decode would have fed, with drafted
future KV entries masked per query (see ``layers._verify_attention``).
Bookkeeping is kept symmetric by NOT claiming the bonus token a
fully-accepted block's last logits would give: both the verify cache and
the draft cache then always hold KV through the sequence's second-last
token, so every round starts with one uniform width-1 draft feed.
Rejected tails roll back through ``pool.rollback`` — lengths truncate
and (paged) tail blocks return to the free list with the row's
reservation re-credited, so speculation never leaks blocks.

Scenario hot-swap (repro.scenario): the batcher can swap the params
tree's SRAM branch over the resident ROM trunk mid-stream.  A swap is a
BARRIER in the same FIFO queue requests ride: it applies at a
decode-step boundary once every in-flight request has retired, so a
request admitted under scenario A decodes entirely under A — bit-
identical to a freshly compiled single-scenario cell — while requests
tagged for B wait behind the barrier.  The swap is one donated combine
(``scenario.swap_params``: trunk buffers alias through untouched, no
recompile, since the params tree structure is unchanged and the
resident jit executables are reused as-is), then one derivation of the
served branch from the new cores (below), which reads every site's ROM
``C`` and ``U`` once.

Served branch: the batcher keeps the params tree it was given (the
source, which swaps replace) and hands every device call a served tree
built from it once (``rebranch.serving_branch``): each ReBranch site
also holds ``C`` and ``core @ U`` in the activation dtype, so no prefill
chunk or decode step converts or multiplies weights.  The served tree
is built at construction and again after each swap, and references the
source's buffers.  Every site is derived, those whose engine fuses the
branch into its trunk kernel too, which never read the derived leaves.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rebranch
from repro.models import api
from repro.scenario import swap_params
from repro.serve import trace
from repro.serve.pool import SlotPool


@dataclasses.dataclass
class _Swap:
    """A scenario-swap barrier in the admission queue."""
    scenario: str
    branch: object                        # the new branch tree


@dataclasses.dataclass
class Request:
    """One user request plus its scheduling trace."""
    rid: int
    prompt: np.ndarray                    # [S] int32 token ids
    max_new_tokens: int
    eos_id: int | None = None
    scenario: str | None = None           # branch the request runs under
    # filled in by the scheduler:
    tokens: list = dataclasses.field(default_factory=list)
    slot: int | None = None
    submit_step: int = -1                 # scheduler tick at submit
    admit_step: int = -1                  # tick the prefill ran
    finish_step: int = -1                 # tick the last token landed
    submit_s: float = 0.0                 # wall clock, for latency stats
    admit_s: float = 0.0                  # wall clock at the first prefill
    finish_s: float = 0.0
    drafted: int = 0                      # draft tokens verified for this row
    matched: int = 0                      # of those, accepted (drafts only —
                                          # mismatch corrections not counted)

    @property
    def done(self) -> bool:
        return self.finish_step >= 0

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.submit_s


class ContinuousBatcher:
    """Admission queue + decode loop over one model and one KV pool.

    Works over either pool layout (dense :class:`~repro.serve.pool.
    SlotPool` or paged :class:`~repro.serve.pool.PagedPool`) through the
    shared ``try_admit`` / ``adopt`` / ``prepare_step`` / ``release``
    surface.

    ``prefill_chunk`` controls chunked prefill admission: a prompt
    longer than the chunk is prefilled one chunk per scheduler tick,
    interleaved with the batched decode steps, so admitting a long
    prompt never stalls in-flight decodes for its whole prefill.  The
    chunks run against the same solo (batch=1, dense) cache at their
    absolute positions, so the adopted row is bit-identical to a
    whole-prompt solo prefill (regression-tested).  ``None`` -> auto
    (32 for families that support it, see
    ``api.supports_chunked_prefill``); ``0`` -> whole-prompt admission.

    ``spec_k`` turns on speculative decode (see the module docstring):
    up to ``spec_k`` tokens drafted per row per round, one batched
    ``verify_step`` per round, accepted tokens bit-identical to plain
    greedy decode.  ``draft_source`` (optional) replaces the branch-only
    draft model with a callable ``(active: {slot: Request},
    last_tok: [n_slots, 1] int32, k) -> [n_slots, k] int32`` — used by
    benchmarks to dial acceptance rates deterministically; ``None``
    drafts through ``model.draft_decode_step`` over a dense draft KV
    cache that shadows the pool row for row.
    """

    def __init__(self, model, params, pool, *, scenario: str | None = None,
                 prefill_chunk: int | None = None, spec_k: int = 0,
                 draft_source=None):
        self.model = model
        self.pool = pool
        self.scenario = scenario            # live branch label
        self.swap_count = 0                 # swaps applied so far
        # ``_source`` is the params tree as given (what swaps replace);
        # ``params`` the served tree every device call reads, rebuilt
        # from it by _prepare_branch.
        self._source = params
        self.branch_prepares = 0            # served trees built so far
        self._prepare_branch()
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and not api.supports_speculation(model.cfg):
            raise ValueError(
                f"spec_k={spec_k} but {model.cfg.name!r} (family "
                f"{model.cfg.family!r}, sliding_window="
                f"{model.cfg.sliding_window}, kv_lora_rank="
                f"{model.cfg.kv_lora_rank}) cannot speculate: rollback "
                f"needs a full-horizon attention cache, and latent "
                f"attention has no k-token verify "
                f"(api.supports_speculation); pass spec_k=0")
        self.spec_k = int(spec_k)
        self.draft_source = draft_source
        self.spec_rounds = 0                # verify dispatches so far
        self.drafted_total = 0              # draft tokens verified
        self.matched_total = 0              # of those, accepted
        if self.spec_k:
            self._verify = jax.jit(model.verify_step, donate_argnums=(2,))
            if draft_source is None:
                # The draft model's own KV state: a dense cache with one
                # row per pool slot, indexed by the SAME slot ids (the
                # SlotPool here is a plain cache holder — its free list
                # is unused; admission/release stay with self.pool).
                self._draft_prefill = jax.jit(model.draft_prefill)
                self._draft_decode = jax.jit(model.draft_decode_step,
                                             donate_argnums=(2,))
                self._draft_pool = SlotPool(model, pool.n_slots,
                                            pool.max_len, dtype=pool.dtype)
        if prefill_chunk is None:
            prefill_chunk = 32 if api.supports_chunked_prefill(model.cfg) \
                else 0
        elif prefill_chunk and not api.supports_chunked_prefill(model.cfg):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} but {model.cfg.name!r} "
                f"(family {model.cfg.family!r}) cannot chunk prefill — "
                f"ssm/hybrid recurrent state is rebuilt from position 0 "
                f"each prefill call; pass prefill_chunk=0")
        self.prefill_chunk = int(prefill_chunk)
        self._prefill = jax.jit(model.prefill)
        # donate the cache: the pool always replaces it with the returned
        # tree, so decode updates the KV rows in place instead of copying
        # the whole pool every step
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self._queue: collections.deque = collections.deque()
        self._active: dict[int, Request] = {}       # slot -> request
        # in-flight chunked prefill: (req, row, solo_cache, pos) or None
        self._prefilling: tuple | None = None
        # the token column fed to decode_step: one row per slot; free
        # rows carry 0 (their output is masked by never being read)
        self._tok = np.zeros((pool.n_slots, 1), np.int32)
        self._next_rid = 0
        self.step_count = 0
        self._held_seen = None    # the pool's held-expert count, traced

    # -- front door ------------------------------------------------------
    def pending_scenario(self) -> str | None:
        """The branch label after every queued swap barrier applies —
        what a submit() issued now will be admitted under."""
        for item in reversed(self._queue):
            if isinstance(item, _Swap):
                return item.scenario
        return self.scenario

    def swap(self, scenario: str | None, branch) -> None:
        """Queue a branch hot-swap.  FIFO with requests: everything
        submitted before the swap decodes under the old branch,
        everything after under the new one.  The swap applies at a
        decode-step boundary once the in-flight set has drained —
        in-flight requests always finish on their admitted scenario."""
        self._queue.append(_Swap(scenario=scenario, branch=branch))

    def submit(self, prompt, max_new_tokens: int,
               eos_id: int | None = None,
               scenario: str | None = None) -> Request:
        """Queue one request; returns its live :class:`Request` handle.

        Raises at the front door — never mid-decode — for requests that
        could never run: empty prompts, ``max_new_tokens < 1``, totals
        beyond the pool's horizon, and scenario labels that do not
        match the queue tail (swap first; ``LMServer.submit`` does)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        total = prompt.size + max_new_tokens
        if total > self.pool.max_len:
            raise ValueError(
                f"request needs {total} cache slots "
                f"(prompt {prompt.size} + {max_new_tokens} new) but the "
                f"pool was sized for max_len={self.pool.max_len}")
        tail = self.pending_scenario()
        if scenario is not None and scenario != tail:
            raise ValueError(
                f"submit(scenario={scenario!r}) but the queue tail runs "
                f"scenario {tail!r}; call swap({scenario!r}, branch) "
                f"first (LMServer.submit(..., scenario=...) does this "
                f"automatically via the scenario store)")
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      scenario=tail)
        req.submit_step = self.step_count
        req.submit_s = time.perf_counter()
        self._next_rid += 1
        self._queue.append(req)
        return req

    # -- scheduler state -------------------------------------------------
    @property
    def queued(self) -> int:
        return sum(1 for x in self._queue if isinstance(x, Request))

    @property
    def active(self) -> int:
        return len(self._active)

    @property
    def prefilling(self) -> bool:
        """Whether a chunked prefill is in flight (its request is
        neither queued nor active: it holds a pool row but has not
        joined the decode batch)."""
        return self._prefilling is not None

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._active
                and self._prefilling is None)

    @property
    def acceptance_rate(self) -> float:
        """Accepted / verified draft tokens over the batcher's lifetime
        (mismatch corrections — free tokens the verify computes itself —
        are not drafts and count in neither term)."""
        return (self.matched_total / self.drafted_total
                if self.drafted_total else 0.0)

    # -- the loop ----------------------------------------------------------
    def _finish(self, req: Request) -> None:
        req.finish_step = self.step_count
        req.finish_s = time.perf_counter()
        self.pool.release(req.slot)
        del self._active[req.slot]

    def _maybe_retire(self, req: Request) -> None:
        hit_eos = (req.eos_id is not None and req.tokens
                   and req.tokens[-1] == req.eos_id)
        if len(req.tokens) >= req.max_new_tokens or hit_eos:
            self._finish(req)

    def _prepare_branch(self) -> None:
        """Build the served tree from the source: each ReBranch site's
        branch in the activation dtype (``rebranch.serving_branch``), so
        no prefill, decode, verify or draft call converts or multiplies
        weights.  Runs at construction and after every swap."""
        self.params = None                # free the stale derived leaves
        with trace.span("batcher.prepare_branch"):
            self.params, sites, nbytes = rebranch.serving_branch(
                self._source, jnp.dtype(self.model.cfg.dtype))
            trace.annotate(sites=sites, bytes=nbytes)
        self.branch_prepares += 1

    def _apply_swap(self, sw: _Swap) -> None:
        """One donated combine: branch leaves replaced, trunk buffers
        alias through in place (no recompile — the tree structure is
        unchanged so the jitted prefill/decode executables are reused
        as-is), then the served tree is derived again from the new
        branch, which reads each site's ROM ``C`` and ``U``."""
        self._source = swap_params(self._source, sw.branch)
        self._prepare_branch()
        self.scenario = sw.scenario
        self.swap_count += 1

    def _activate(self, req: Request, slot: int, solo, logits) -> None:
        """Adopt a finished solo prefill into the pool and put the
        request into the decode batch (its first token comes from the
        prefill logits, exactly like the standalone path)."""
        with trace.span("pool.adopt", rid=req.rid):
            self.pool.adopt(slot, solo)
        if self.spec_k and self.draft_source is not None:
            pass                          # injected drafter: no draft KV
        elif self.spec_k:
            # Shadow the row in the draft model's cache: one whole-prompt
            # branch-only prefill (cheap — the trunks are skipped), so
            # the draft cache holds KV for the prompt and starts every
            # round one token behind the sequence tail, exactly like the
            # verify cache.  Chunking is unnecessary at draft cost.
            d_solo = self._draft_pool.solo_cache()
            _, d_solo = self._draft_prefill(
                self.params, {"tokens": jnp.asarray(req.prompt[None])},
                d_solo)
            self._draft_pool.adopt(slot, d_solo)
        with trace.span("batcher.first_token", rid=req.rid):
            first = int(jnp.argmax(logits[0, -1]))
        trace.record("request.prefill", req.admit_s * 1e9,
                     time.perf_counter_ns(), rid=req.rid)
        req.slot = slot
        req.admit_step = self.step_count
        req.tokens.append(first)
        self._tok[slot, 0] = first
        self._active[slot] = req
        self._maybe_retire(req)           # 1-token requests finish here

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the in-flight prefill.  Each chunk extends
        the same solo cache at its absolute offset, so the finished row
        is bit-identical to a whole-prompt solo prefill; the final
        chunk's logits yield the first token and the row activates."""
        req, slot, solo, pos = self._prefilling
        end = min(pos + self.prefill_chunk, req.prompt.size)
        logits, solo = self._run_prefill(req, solo, pos, end)
        if end < req.prompt.size:
            self._prefilling = (req, slot, solo, end)
        else:
            self._prefilling = None
            self._activate(req, slot, solo, logits)

    def _admit(self) -> None:
        """FIFO admission against the pool's capacity.

        The head request admits only when the pool can GUARANTEE it
        (``try_admit``: a free row, and — paged — enough unreserved
        blocks for prompt + max_new_tokens); admission stays strictly
        FIFO, so a big request blocks the queue rather than starving.
        Prompts longer than ``prefill_chunk`` prefill one chunk per
        tick (at most one such prefill in flight; decode keeps running
        between chunks).  A queued _Swap barrier applies only once
        in-flight work has drained — active rows AND any chunked
        prefill, which must finish under the params it started with."""
        if self._prefilling is not None:
            self._advance_prefill()
            if self._prefilling is not None:
                return            # still mid-prompt; FIFO order holds
        while self._queue:
            head = self._queue[0]
            if isinstance(head, _Swap):
                if self._active or self._prefilling is not None:
                    return        # in-flight work finishes on its branch
                self._apply_swap(self._queue.popleft())
                continue
            slot = self.pool.try_admit(head.prompt.size
                                       + head.max_new_tokens)
            if slot is None:
                return            # work-conserving: wait for capacity
            req = self._queue.popleft()
            solo = self.pool.solo_cache()
            if self.prefill_chunk and req.prompt.size > self.prefill_chunk:
                self._prefilling = (req, slot, solo, 0)
                self._advance_prefill()       # first chunk, this tick
                if self._prefilling is not None:
                    return
                continue
            logits, solo = self._run_prefill(req, solo, 0, req.prompt.size)
            self._activate(req, slot, solo, logits)

    def _run_prefill(self, req: Request, solo, pos: int, end: int):
        """One prefill call over ``prompt[pos:end]`` into the solo
        cache; the first call of a request stamps its admission."""
        if pos == 0:
            req.admit_s = time.perf_counter()
            trace.record("request.queue", req.submit_s * 1e9,
                         req.admit_s * 1e9, rid=req.rid)
        with trace.span("batcher.prefill", rid=req.rid, start=pos, end=end):
            return self._prefill(
                self.params,
                {"tokens": jnp.asarray(req.prompt[None, pos:end])}, solo)

    def step(self) -> bool:
        """One scheduler tick: retire / admit at the boundary (one
        prefill chunk at most), then one batched decode step — or, in
        speculative mode, one draft+verify round.  Returns False once
        idle."""
        with trace.span("batcher.step"):
            with trace.span("batcher.admit"):
                self._admit()
            if not self._active:
                return not self.idle
            if self.spec_k:
                return self._spec_step()
            # paged pools grant each row's next block here; dense no-op
            with trace.span("pool.prepare_step"):
                self.pool.prepare_step()
            with trace.span("batcher.decode",
                            rows=len(self._active)) as decode_span:
                if trace.enabled():
                    trace.annotate(**self.pool.counts())
                logits, cache = self._decode(
                    self.params, jnp.asarray(self._tok), self.pool.cache)
            self.pool.cache = cache
            with trace.span("batcher.sample"):
                nxt = jnp.argmax(logits[:, -1, :], axis=-1)
                if trace.enabled() and "counters" in cache:
                    nxt = self._annotate_held(decode_span, nxt, cache)
                else:
                    self._held_seen = None
                nxt = np.asarray(nxt, np.int32)
            self.step_count += 1
            with trace.span("batcher.retire"):
                for slot, req in list(self._active.items()):
                    req.tokens.append(int(nxt[slot]))
                    self._tok[slot, 0] = nxt[slot]
                    self._maybe_retire(req)
            return not self.idle

    def _annotate_held(self, span, nxt, cache):
        """While the recorder is on: the decode span gains
        ``held_tokens``, the token assignments its step's held experts
        computed, read with the sampled tokens in one transfer (the
        sample waits for the step anyway)."""
        held = cache["counters"]["held_tokens"]
        nxt, total = jax.device_get((nxt, jnp.sum(held)))
        total = int(total)
        if self._held_seen is not None:
            trace.annotate_span(span, held_tokens=total - self._held_seen)
        self._held_seen = total
        return nxt

    def _spec_step(self) -> bool:
        """One draft+verify round over the active batch.

        k is clamped to the smallest remaining token budget across
        active rows: every row then needs at most k more cache
        positions, which its admission already reserved — verify writes
        can never wrap or outrun the pool.  The round: k width-1 draft
        feeds propose d[0..k-1]; verify runs the [N, k] block
        [last_token, d[0..k-2]] through the full cell; row-wise, the
        longest drafted prefix matching the verify argmaxes is accepted
        plus the first mismatch's correction (so every round lands 1..k
        tokens, and a k=1 round IS a plain decode step, bit for bit).
        Rejected tails roll back — verify cache AND draft cache — to
        the accepted length.
        """
        k = min(self.spec_k,
                min(r.max_new_tokens - len(r.tokens)
                    for r in self._active.values()))
        with trace.span("batcher.draft", rows=len(self._active), k=k):
            drafts = self._draft(k)
        # one batched verify over [last_token, d0..d_{k-2}]
        block = np.concatenate([self._tok, drafts[:, :k - 1]], axis=1)
        with trace.span("pool.prepare_step"):
            self.pool.prepare_tokens(k)
        with trace.span("batcher.verify", rows=len(self._active), k=k):
            if trace.enabled():
                trace.annotate(**self.pool.counts())
            logits, cache = self._verify(
                self.params, jnp.asarray(block), self.pool.cache)
        self.pool.cache = cache
        with trace.span("batcher.sample"):
            truth = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.step_count += 1
        self.spec_rounds += 1
        with trace.span("batcher.retire"):
            roll = self._accept(drafts, truth, k)
        with trace.span("pool.rollback", rows=len(roll)):
            self.pool.rollback(roll)
            if self.draft_source is None:
                self._draft_pool.rollback(roll)
        return not self.idle

    def _draft(self, k: int) -> np.ndarray:
        """[n_slots, k] drafted tokens: the injected ``draft_source``'s,
        or k width-1 feeds of the branch-only draft model."""
        n = self.pool.n_slots
        if self.draft_source is not None:
            return np.asarray(
                self.draft_source(dict(self._active), self._tok.copy(), k),
                np.int32).reshape(n, k)
        drafts = np.zeros((n, k), np.int32)
        tok = self._tok
        for j in range(k):
            d_logits, d_cache = self._draft_decode(
                self.params, jnp.asarray(tok), self._draft_pool.cache)
            self._draft_pool.cache = d_cache
            nxt = np.asarray(jnp.argmax(d_logits[:, -1, :], axis=-1),
                             np.int32)
            drafts[:, j] = nxt
            tok = nxt[:, None]
        return drafts

    def _accept(self, drafts, truth, k: int) -> dict[int, int]:
        """Greedy accept-longest-prefix per row; retires finished rows
        and returns ``{row: new_length}`` for survivors to truncate."""
        roll: dict[int, int] = {}
        for slot, req in list(self._active.items()):
            d, c = drafts[slot], truth[slot]
            j = int(np.argmax(d != c)) if bool((d != c).any()) else k
            accepted = [int(t) for t in c[:min(j + 1, k)]]
            req.drafted += k
            req.matched += j if j < k else k
            self.drafted_total += k
            self.matched_total += j if j < k else k
            old_len = req.prompt.size + len(req.tokens) - 1
            for t in accepted:
                req.tokens.append(t)
                if req.eos_id is not None and t == req.eos_id:
                    break                 # EOS mid-block: drop the rest
            self._tok[slot, 0] = req.tokens[-1]
            new_len = req.prompt.size + len(req.tokens) - 1
            self._maybe_retire(req)       # retirement releases the row:
            if slot in self._active and new_len != old_len + k:
                roll[slot] = new_len      # survivors truncate the tail
        return roll

    def drain(self, max_steps: int | None = None) -> int:
        """Run until every submitted request finished; returns the
        number of decode steps taken.  ``max_steps`` guards tests
        against scheduler bugs (raises instead of spinning)."""
        start = self.step_count
        while not self.idle:
            if max_steps is not None and \
                    self.step_count - start >= max_steps:
                raise RuntimeError(
                    f"drain() exceeded {max_steps} steps with "
                    f"{self.queued} queued / {self.active} active — "
                    f"scheduler stuck?")
            self.step()
        return self.step_count - start

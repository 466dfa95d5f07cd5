"""Serving runtime: registry, KV pools, continuous-batching scheduler.

The load-bearing invariants (ISSUE 7 + ISSUE 9):
  * batch occupancy never exceeds the pool size;
  * admission is FIFO and no request starves — every submitted request
    finishes within a bounded number of scheduler ticks;
  * each request's serve output is BIT-identical to a solo
    prefill+decode_step run of the same prompt (continuous batching
    changes scheduling, never results) — over the dense SlotPool, over
    the paged block pool, and with prefill split into chunks;
  * paged admission is conservative: a request admits only when its
    whole reservation fits, so decode can never deadlock on blocks;
  * cache/batch geometry mismatches fail at the CompiledModel surface
    with a message naming both shapes, not deep inside XLA.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, deploy, serve
from repro.core import rebranch
from repro.models import api, cnn
from repro.serve.pool import (PagedPool, SlotPool, cache_bytes_per_slot,
                              suggest_paged)
from repro.serve.scheduler import ContinuousBatcher

MODEL_ID = "gemma-2b-smoke"
MAX_LEN = 48
# batched vs solo float32 logits: max |diff| over max |logit|, per row
LOGIT_RTOL = 1e-5


@pytest.fixture(scope="module")
def cell():
    model, plan = serve.compile_entry(MODEL_ID)
    params = model.init(jax.random.PRNGKey(0))
    return model, plan, params


def _prompts(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    # varied lengths: exercises per-row cache state under batching
    return [rng.integers(0, vocab, size=6 + (i % 4)) for i in range(n)]


def _solo_decode(model, params, prompt, n_new):
    """The reference path: batch=1 prefill + decode loop."""
    cache = model.init_cache(1, MAX_LEN, dtype=jnp.float32)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(np.asarray(prompt)[None])}, cache)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for _ in range(n_new - 1):
        logits, cache = jax.jit(model.decode_step)(
            params, jnp.asarray([[tok]], jnp.int32), cache)
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_unknown_id_raises_with_registered_set(self):
        with pytest.raises(KeyError, match="gemma-2b-smoke"):
            serve.resolve("no-such-model")

    def test_compile_is_resident(self):
        m1, p1 = serve.compile_entry(MODEL_ID)
        m2, p2 = serve.compile_entry(MODEL_ID)
        assert m1 is m2                     # one cell per id per process

    def test_duplicate_register_needs_override(self):
        entry = serve.resolve(MODEL_ID)
        with pytest.raises(ValueError, match="already registered"):
            serve.register(entry)
        serve.register(entry, override=True)   # idempotent with override

    def test_builtin_zoo_covers_lms_and_cnns(self):
        ids = serve.registered_ids()
        assert "gemma-2b-smoke" in ids and "falcon-mamba-7b-smoke" in ids
        assert "darknet19-32" in ids and "vgg8-32" in ids

    def test_lm_entries_carry_a_plan(self, cell):
        _, plan, _ = cell
        assert plan is not None and plan.model == "gemma_2b_smoke"

    def test_reregister_drops_resident_cell(self):
        serve.register(serve.ModelEntry(
            "rereg-test",
            config=lambda: cnn.CNNConfig(name="vgg8", input_size=16)),
            override=True)
        m1, _ = serve.compile_entry("rereg-test")
        assert m1.cfg.input_size == 16
        serve.register(serve.ModelEntry(
            "rereg-test",
            config=lambda: cnn.CNNConfig(name="vgg8", input_size=32)),
            override=True)
        m2, _ = serve.compile_entry("rereg-test")
        assert m2 is not m1 and m2.cfg.input_size == 32

    def test_compile_racing_reregister_never_publishes_stale_cell(self):
        """A re-register landing mid-compile must not let the in-flight
        compile publish the OLD entry's cell (it would silently serve a
        stale config).  The entry's config factory runs inside
        compile_entry, which lets the race be staged deterministically:
        the old factory re-registers the id before returning."""
        def old_factory():
            serve.register(serve.ModelEntry(
                "race-test",
                config=lambda: cnn.CNNConfig(name="vgg8", input_size=32)),
                override=True)
            return cnn.CNNConfig(name="vgg8", input_size=16)

        serve.register(serve.ModelEntry("race-test", config=old_factory),
                       override=True)
        model, _ = serve.compile_entry("race-test")
        assert model.cfg.input_size == 32    # stale 16px cell discarded


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------

class TestSlotPool:
    def test_alloc_release_cycle(self, cell):
        model, _, _ = cell
        pool = SlotPool(model, 3, MAX_LEN)
        slots = [pool.alloc() for _ in range(3)]
        assert sorted(slots) == [0, 1, 2]
        assert pool.alloc() is None and pool.occupancy == 3
        pool.release(slots[1])
        assert pool.free_slots == 1 and pool.alloc() == slots[1]

    def test_double_release_raises(self, cell):
        model, _, _ = cell
        pool = SlotPool(model, 2, MAX_LEN)
        s = pool.alloc()
        pool.release(s)
        with pytest.raises(ValueError, match="double-released"):
            pool.release(s)

    def test_adopt_copies_the_row_bitwise(self, cell):
        model, _, params = cell
        pool = SlotPool(model, 3, MAX_LEN)
        prompt = _prompts(1, model.cfg.vocab_size)[0]
        solo = pool.solo_cache()
        _, solo = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(prompt[None])}, solo)
        pool.adopt(1, solo)
        axis = 1 if model.cfg.scan_layers else 0
        for pl, sl in zip(jax.tree.leaves(pool.cache),
                          jax.tree.leaves(solo)):
            row = jnp.take(pl, 1, axis=axis)
            np.testing.assert_array_equal(
                np.asarray(row),
                np.asarray(jnp.take(sl, 0, axis=axis)))

    def test_suggest_slots_respects_budget(self, cell):
        model, plan, _ = cell
        per_slot = cache_bytes_per_slot(model, MAX_LEN)
        assert per_slot > 0
        tiny = serve.suggest_slots(model, plan, MAX_LEN,
                                   sram_capacity_bytes=0)
        assert tiny == 1                     # never a zero-slot pool
        big = serve.suggest_slots(model, plan, MAX_LEN,
                                  sram_capacity_bytes=1 << 40)
        assert big == 64                     # capped
        mid = serve.suggest_slots(model, plan, MAX_LEN,
                                  sram_capacity_bytes=per_slot * 5)
        assert 1 <= mid <= 5


# ---------------------------------------------------------------------------
# continuous batching scheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def _served(self, cell, n_req, n_slots, gens=None, track=None):
        model, _, params = cell
        pool = SlotPool(model, n_slots, MAX_LEN)
        b = ContinuousBatcher(model, params, pool)
        prompts = _prompts(n_req, model.cfg.vocab_size)
        gens = gens or [5] * n_req
        reqs = [b.submit(p, g) for p, g in zip(prompts, gens)]
        while not b.idle:
            b.step()
            if track is not None:
                track(b)
            assert b.step_count < 500, "scheduler stuck"
        return b, reqs, prompts

    def test_occupancy_never_exceeds_pool(self, cell):
        peaks = []
        b, reqs, _ = self._served(
            cell, n_req=6, n_slots=2,
            track=lambda b: peaks.append(b.active))
        assert max(peaks) <= 2
        assert all(r.done for r in reqs)

    def test_no_starvation_fifo(self, cell):
        """With a pool of 2 and 6 equal requests, admission must proceed
        in submit order and every request must finish within the bound
        of ceil(n/slots) generations."""
        b, reqs, _ = self._served(cell, n_req=6, n_slots=2)
        admits = [r.admit_step for r in reqs]
        assert admits == sorted(admits)          # FIFO admission
        for r in reqs:
            assert r.done
            # waited at most ceil(6/2)=3 generation rounds of 5 tokens
            assert r.finish_step - r.submit_step <= 3 * 5

    def test_bit_identical_to_solo(self, cell):
        """The headline invariant: continuous batching (varied prompt
        lengths, staggered joins, mid-batch retirement) returns exactly
        the solo path's tokens for every request."""
        model, _, params = cell
        # heterogeneous gen lengths force mid-batch retire + late joins
        gens = [4, 7, 3, 6, 5]
        b, reqs, prompts = self._served(cell, n_req=5, n_slots=2,
                                        gens=gens)
        for r, p, g in zip(reqs, prompts, gens):
            assert r.tokens == _solo_decode(model, params, p, g), \
                f"request {r.rid} diverged from solo decode"

    def test_late_submission_joins_running_batch(self, cell):
        model, _, params = cell
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, params, pool)
        prompts = _prompts(2, model.cfg.vocab_size)
        r1 = b.submit(prompts[0], 8)
        for _ in range(3):
            b.step()
        r2 = b.submit(prompts[1], 4)         # joins at a step boundary
        b.drain(max_steps=100)
        assert r2.admit_step > r1.admit_step
        assert r1.tokens == _solo_decode(model, params, prompts[0], 8)
        assert r2.tokens == _solo_decode(model, params, prompts[1], 4)

    def test_eos_retires_early(self, cell):
        model, _, params = cell
        prompt = _prompts(1, model.cfg.vocab_size)[0]
        ref = _solo_decode(model, params, prompt, 8)
        eos = ref[2]                          # hit no later than token 3
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, params, pool)
        r = b.submit(prompt, 8, eos_id=eos)
        b.drain(max_steps=100)
        # retire at the FIRST occurrence (eos may repeat earlier in ref)
        assert r.tokens == ref[:ref.index(eos) + 1]
        assert len(r.tokens) < 8
        assert pool.occupancy == 0            # slot returned

    def test_submit_validation(self, cell):
        model, _, params = cell
        b = ContinuousBatcher(model, params, SlotPool(model, 1, MAX_LEN))
        with pytest.raises(ValueError, match="empty prompt"):
            b.submit([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            b.submit([1, 2], 0)
        with pytest.raises(ValueError, match="max_len"):
            b.submit(list(range(40)), 20)     # 60 > MAX_LEN


# ---------------------------------------------------------------------------
# front door (async LM + forward-only CNN)
# ---------------------------------------------------------------------------

class TestFrontDoor:
    def test_async_generate_batches_concurrent_callers(self, cell):
        model, _, params = cell
        srv = serve.LMServer(model, params, n_slots=4, max_len=MAX_LEN)
        prompts = _prompts(3, model.cfg.vocab_size, seed=7)

        async def main():
            return await asyncio.gather(
                *[srv.generate(p, 5) for p in prompts])

        outs = asyncio.run(main())
        for p, got in zip(prompts, outs):
            assert got == _solo_decode(model, params, p, 5)

    def test_cnn_front_door_matches_solo_forward(self):
        srv = serve.load("vgg8-32", n_slots=4, key=jax.random.PRNGKey(1))
        assert isinstance(srv, serve.CNNServer)
        rng = np.random.default_rng(3)
        imgs = rng.normal(size=(6, 32, 32, 3)).astype(np.float32)
        got = srv.submit(imgs)
        assert got.shape == (6, srv.model.cfg.num_classes)
        # chunking + padding must be INVISIBLE: rows equal the same
        # images run through the same fixed-geometry forward, bitwise
        pad = jnp.concatenate(
            [jnp.asarray(imgs[4:]), jnp.zeros((2, 32, 32, 3))], 0)
        ref = np.concatenate([
            np.asarray(srv._forward(srv.params, jnp.asarray(imgs[:4]))),
            np.asarray(srv._forward(srv.params, pad))[:2]], 0)
        np.testing.assert_array_equal(got, ref)
        for i in range(6):   # and close to the solo batch=1 forward
            solo = np.asarray(srv.model.forward(
                srv.params, jnp.asarray(imgs[i:i + 1])))
            np.testing.assert_allclose(got[i], solo[0], rtol=2e-3,
                                       atol=2e-3)

    def test_load_lm_sizes_pool_from_plan(self):
        srv = serve.load(MODEL_ID, max_len=MAX_LEN)
        assert isinstance(srv, serve.LMServer)
        assert 1 <= srv.pool.n_slots <= 64


# ---------------------------------------------------------------------------
# cache/batch geometry validation at the CompiledModel surface
# ---------------------------------------------------------------------------

class TestCacheGeometry:
    @pytest.fixture(scope="class")
    def lm(self):
        cfg = configs.get_smoke("gemma_2b")
        model = deploy.compile_model(cfg)
        return model, model.init(jax.random.PRNGKey(0))

    def test_prefill_batch_mismatch_names_both_shapes(self, lm):
        model, params = lm
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        with pytest.raises(ValueError,
                           match=r"batch=2.*batch=4") as e:
            model.prefill(params, {"tokens": jnp.zeros((4, 8), jnp.int32)},
                          cache)
        assert "init_cache" in str(e.value)

    def test_decode_batch_mismatch(self, lm):
        model, params = lm
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        with pytest.raises(ValueError, match=r"batch=2.*batch=3"):
            model.decode_step(params, jnp.zeros((3, 1), jnp.int32), cache)

    def test_decode_multi_token_rejected(self, lm):
        model, params = lm
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        with pytest.raises(ValueError, match="ONE token"):
            model.decode_step(params, jnp.zeros((2, 4), jnp.int32), cache)

    def test_prompt_longer_than_horizon(self, lm):
        model, params = lm
        cache = model.init_cache(2, 16, dtype=jnp.float32)
        with pytest.raises(ValueError, match="horizon"):
            model.prefill(params,
                          {"tokens": jnp.zeros((2, 20), jnp.int32)}, cache)

    def test_raises_under_jit_too(self, lm):
        model, params = lm
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        with pytest.raises(ValueError, match="batch"):
            jax.jit(model.decode_step)(
                params, jnp.zeros((5, 1), jnp.int32), cache)

    def test_geometry_helper_all_families(self):
        for arch, horizon_none in [("falcon_mamba_7b", True),
                                   ("hymba_1_5b", False),
                                   ("qwen2_moe_a2_7b", False)]:
            cfg = configs.get_smoke(arch)
            cache = api.init_cache(cfg, 3, 16, jnp.float32)
            batch, horizon = api.cache_geometry(cfg, cache)
            assert batch == 3
            assert (horizon is None) == horizon_none
            if horizon is not None:
                assert horizon == 16

    def test_valid_geometry_passes(self, lm):
        model, params = lm
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        logits, cache = model.prefill(
            params, {"tokens": jnp.zeros((2, 8), jnp.int32)}, cache)
        logits, _ = model.decode_step(
            params, jnp.zeros((2, 1), jnp.int32), cache)
        assert logits.shape[0] == 2


# ---------------------------------------------------------------------------
# paged KV pool (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------

class TestPagedPool:
    BS = 8          # block size; MAX_LEN=48 -> 6 logical blocks per row

    def _pool(self, model, rows=3, blocks=12):
        return PagedPool(model, rows, blocks, self.BS, MAX_LEN)

    def test_admit_reserves_conservatively(self, cell):
        """Admission must refuse unless the WHOLE request (prompt +
        max_new) is guaranteed blocks — over-admitting would deadlock
        decode mid-request on an empty free list."""
        model, _, _ = cell
        pool = self._pool(model, rows=3, blocks=7)
        r1 = pool.try_admit(MAX_LEN)          # reserves 6 of 7 blocks
        assert r1 is not None
        assert pool.try_admit(2 * self.BS) is None   # 2 > 7-6 remaining
        assert pool.try_admit(self.BS) is not None   # exactly fits
        pool.release(r1)
        assert pool.try_admit(2 * self.BS) is not None

    def test_rows_and_blocks_both_gate_admission(self, cell):
        model, _, _ = cell
        pool = self._pool(model, rows=1, blocks=12)
        assert pool.try_admit(8) is not None
        assert pool.try_admit(8) is None      # blocks free, rows gone
        with pytest.raises(ValueError, match="max_len"):
            pool.try_admit(MAX_LEN + 1)       # could never fit

    def test_release_returns_blocks_and_row(self, cell):
        model, _, _ = cell
        pool = self._pool(model)
        row = pool.try_admit(20)
        pool.release(row)
        assert pool.free_slots == 3 and pool.blocks_in_use == 0
        assert pool.blocks_reserved == 0
        with pytest.raises(ValueError, match="double-released"):
            pool.release(row)

    def test_geometry_errors(self, cell):
        model, _, _ = cell
        with pytest.raises(ValueError, match="does not divide"):
            PagedPool(model, 2, 12, 7, MAX_LEN)       # 7 ∤ 48
        with pytest.raises(ValueError, match="one full-horizon"):
            PagedPool(model, 2, 3, self.BS, MAX_LEN)  # 3 < 6 blocks
        cfg = configs.get_smoke("falcon_mamba_7b")
        assert not api.supports_paging(cfg)
        with pytest.raises(ValueError, match="paged"):
            api.init_paged_cache(cfg, 2, 8, 8, 32)

    def test_adopt_scatters_the_row_bitwise(self, cell):
        """The gathered logical view of an adopted row must equal the
        dense solo cache at every valid position — paging moves bytes,
        never bits."""
        from repro.models.layers import _gather_paged
        model, _, params = cell
        pool = self._pool(model)
        prompt = _prompts(1, model.cfg.vocab_size)[0]
        solo = pool.solo_cache()
        _, solo = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(prompt[None])}, solo)
        row = pool.try_admit(prompt.size + 4)
        pool.adopt(row, solo)
        axis = 1 if model.cfg.scan_layers else 0
        length = int(np.asarray(
            api._first_layer(solo)["length"]).reshape(-1)[0])
        first = api._first_layer(pool.cache)
        k_phys = jnp.take(first["k"], 0, axis=0) if axis else first["k"]
        table = jnp.take(first["table"], 0, axis=0) if axis \
            else first["table"]
        view = _gather_paged(k_phys, table)[row]
        solo_k = api._first_layer(solo)["k"]
        solo_row = jnp.take(solo_k, 0, axis=0)[0] if axis \
            else solo_k[0]
        np.testing.assert_array_equal(np.asarray(view[:length]),
                                      np.asarray(solo_row[:length]))

    def test_suggest_paged_matches_dense_budget(self, cell):
        model, plan, _ = cell
        rows, blocks, bs = suggest_paged(model, plan, MAX_LEN,
                                         sram_capacity_bytes=1 << 30)
        assert MAX_LEN % bs == 0
        assert blocks * bs >= MAX_LEN          # at least one full request
        assert 1 <= rows <= 64


class TestPagedScheduler:
    def _batcher(self, cell, rows=4, blocks=18, bs=8, chunk=None):
        model, _, params = cell
        pool = PagedPool(model, rows, blocks, bs, MAX_LEN)
        return pool, ContinuousBatcher(model, params, pool,
                                       prefill_chunk=chunk)

    def test_bit_identical_to_solo_over_paged_pool(self, cell):
        """The headline invariant survives paging: mixed prompt
        lengths, staggered joins, mid-batch retirement through block
        tables return exactly the solo path's tokens."""
        model, _, params = cell
        pool, b = self._batcher(cell)
        prompts = _prompts(5, model.cfg.vocab_size)
        gens = [4, 7, 3, 6, 5]
        reqs = [b.submit(p, g) for p, g in zip(prompts, gens)]
        b.drain(max_steps=200)
        for r, p, g in zip(reqs, prompts, gens):
            assert r.tokens == _solo_decode(model, params, p, g), \
                f"request {r.rid} (len {p.size}) diverged over paging"
        assert pool.blocks_in_use == 0 and pool.occupancy == 0

    def test_blocks_grow_on_demand(self, cell):
        """Adoption grants only the prompt's blocks; decode growth
        grants the rest one block at a time (early EOS never
        materialises the reservation's tail)."""
        model, _, params = cell
        pool, b = self._batcher(cell, rows=2, blocks=12, bs=4)
        b.submit(_prompts(1, model.cfg.vocab_size)[0], 10)  # 6-token prompt
        b.step()                      # admitted: 2 blocks cover prompt+1
        start = pool.blocks_in_use
        assert start <= 2
        high = start
        while not b.idle:
            b.step()
            high = max(high, pool.blocks_in_use)
        assert high > start           # grew during decode
        assert pool.blocks_in_use == 0

    def test_admission_waits_for_blocks_not_just_rows(self, cell):
        """With rows to spare but blocks exhausted, later requests must
        queue (FIFO, work-conserving) and admit once blocks free."""
        model, _, params = cell
        pool, b = self._batcher(cell, rows=4, blocks=6, bs=8)
        prompts = _prompts(3, model.cfg.vocab_size)
        r1 = b.submit(prompts[0], MAX_LEN - prompts[0].size)  # all 6 blocks
        r2 = b.submit(prompts[1], 4)
        b.step()
        assert r1.admit_step >= 0 and r2.admit_step < 0
        assert pool.free_slots == 3          # rows were never the limit
        b.drain(max_steps=200)
        assert r2.done
        assert r2.admit_step > r1.admit_step


# ---------------------------------------------------------------------------
# chunked prefill admission (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_chunked_prefill_bit_identical(self, cell):
        """A prompt prefilled in chunks across scheduler ticks must
        adopt a row bit-identical to the whole-prompt solo prefill —
        every chunk extends the same cache at absolute positions."""
        model, _, params = cell
        for pool in (SlotPool(model, 2, MAX_LEN),
                     PagedPool(model, 2, 14, 8, MAX_LEN)):
            b = ContinuousBatcher(model, params, pool, prefill_chunk=4)
            prompts = _prompts(3, model.cfg.vocab_size, seed=3)
            reqs = [b.submit(p, 5) for p in prompts]
            b.drain(max_steps=200)
            for r, p in zip(reqs, prompts):
                assert r.tokens == _solo_decode(model, params, p, 5), \
                    f"chunked prefill diverged ({type(pool).__name__})"

    def test_prefill_chunks_interleave_with_decode(self, cell):
        """Admitting a long prompt must not stall in-flight decodes:
        with chunk=2, an active request keeps gaining tokens on the
        ticks the new prompt's chunks run."""
        model, _, params = cell
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, params, pool, prefill_chunk=2)
        prompts = _prompts(2, model.cfg.vocab_size, seed=9)
        r1 = b.submit(prompts[0], 12)
        ticks = 0
        while r1.admit_step < 0:                  # r1's own chunks run
            b.step()
            ticks += 1
            assert ticks < 20
        r2 = b.submit(prompts[1], 4)              # 7 tokens: 4 chunks
        grew = []
        while b.prefilling or r2.admit_step < 0:
            before = len(r1.tokens)
            b.step()
            grew.append(len(r1.tokens) > before)
            ticks += 1
            assert ticks < 100
        assert grew and all(grew), \
            "decode stalled during chunked prefill"
        b.drain(max_steps=100)
        assert r1.tokens == _solo_decode(model, params, prompts[0], 12)
        assert r2.tokens == _solo_decode(model, params, prompts[1], 4)

    def test_swap_barrier_waits_for_inflight_prefill(self, cell):
        """A scenario swap queued behind a chunk-prefilling request
        must not apply until that prefill (and its decode) finishes —
        chunks after the swap would run under the wrong params."""
        model, _, pA = cell
        brB = jax.tree.map(
            lambda x: x + jnp.asarray(0.02, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            rebranch.partition(pA)[0])
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, jax.tree.map(jnp.array, pA), pool,
                              scenario="a", prefill_chunk=2)
        prompt = _prompts(1, model.cfg.vocab_size, seed=13)[0]
        r1 = b.submit(prompt, 4, scenario="a")
        b.step()                               # first chunk only
        assert b.prefilling
        b.swap("b", brB)
        b.step()
        assert b.scenario == "a"               # barrier held
        b.drain(max_steps=100)
        assert b.scenario == "b" and b.swap_count == 1
        assert r1.tokens == _solo_decode(model, pA, prompt, 4)

    def test_chunking_rejected_for_recurrent_families(self):
        cfg = configs.get_smoke("falcon_mamba_7b")
        assert not api.supports_chunked_prefill(cfg)
        model = deploy.compile_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pool = SlotPool(model, 1, 32)
        b = ContinuousBatcher(model, params, pool)      # auto -> 0
        assert b.prefill_chunk == 0
        with pytest.raises(ValueError, match="cannot chunk"):
            ContinuousBatcher(model, params, pool, prefill_chunk=8)


# ---------------------------------------------------------------------------
# paged cache geometry at the CompiledModel surface
# ---------------------------------------------------------------------------

class TestPagedGeometry:
    def test_paged_cache_reports_logical_geometry(self, cell):
        model, _, _ = cell
        cache = model.init_paged_cache(3, 10, 8, MAX_LEN)
        batch, horizon = api.cache_geometry(model.cfg, cache)
        assert batch == 3 and horizon == MAX_LEN

    def test_prefill_on_paged_cache_names_the_adopt_path(self, cell):
        model, _, params = cell
        cache = model.init_paged_cache(2, 10, 8, MAX_LEN)
        with pytest.raises(ValueError, match="adopt"):
            model.prefill(params,
                          {"tokens": jnp.zeros((2, 8), jnp.int32)}, cache)

    def test_decode_batch_mismatch_names_block_table_rows(self, cell):
        model, _, params = cell
        cache = model.init_paged_cache(2, 10, 8, MAX_LEN)
        with pytest.raises(ValueError,
                           match=r"block-table rows") as e:
            model.decode_step(params, jnp.zeros((5, 1), jnp.int32), cache)
        assert "init_paged_cache" in str(e.value)

    def test_block_size_must_divide_max_len(self, cell):
        model, _, _ = cell
        with pytest.raises(ValueError, match="does not divide"):
            model.init_paged_cache(2, 10, 7, MAX_LEN)


# ---------------------------------------------------------------------------
# the per-row ring-slot decode fix (serve-path bug)
# ---------------------------------------------------------------------------

class TestPerRowCacheRows:
    def test_mixed_length_rows_decode_independently(self, cell):
        """Rows at different lengths in ONE cache must each write their
        own ring slot: before the fix, every row wrote row 0's slot,
        corrupting any batch whose lengths diverged (exactly the
        continuous-batching state).

        Batched vs solo is a tolerance, not bitwise: XLA may reduce a
        batch-3 dot in another order than a batch-1 one (measured on CPU:
        up to 1.8e-7 of the row's largest float32 logit).  A row that
        read another row's slot is off by the logits' own scale, far
        above ``LOGIT_RTOL``; greedy tokens must agree exactly."""
        model, _, params = cell
        prompts = _prompts(3, model.cfg.vocab_size, seed=11)  # 6,7,8 long
        solo_caches = []
        toks = []
        for p in prompts:
            c = model.init_cache(1, MAX_LEN, dtype=jnp.float32)
            lg, c = jax.jit(model.prefill)(
                params, {"tokens": jnp.asarray(p[None])}, c)
            solo_caches.append(c)
            toks.append(int(jnp.argmax(lg[0, -1])))
        pool = SlotPool(model, 3, MAX_LEN)
        for i, c in enumerate(solo_caches):
            pool.adopt(i, c)
        tok = jnp.asarray(np.asarray(toks, np.int32)[:, None])
        batched_logits, _ = jax.jit(model.decode_step)(
            params, tok, pool.cache)
        for i in range(3):
            solo_logits, _ = jax.jit(model.decode_step)(
                params, tok[i:i + 1], solo_caches[i])
            got = np.asarray(batched_logits[i])
            want = np.asarray(solo_logits[0])
            err = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert err <= LOGIT_RTOL, \
                f"row {i} (len {prompts[i].size}) diverged: rel err {err}"
            np.testing.assert_array_equal(
                np.argmax(got, -1), np.argmax(want, -1),
                err_msg=f"row {i} greedy token diverged")

# ---------------------------------------------------------------------------
# speculative decode (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------

def _oracle_draft(model, params, prompts, gens, wrong_every=None):
    """A ``draft_source`` proposing the known greedy continuation.

    ``wrong_every=j`` corrupts every j-th generated position (j=1 means
    every proposal is wrong); ``None`` proposes perfectly.  Returns the
    draft fn plus the solo references for parity assertions.
    """
    refs = [_solo_decode(model, params, p, g)
            for p, g in zip(prompts, gens)]
    vocab = model.cfg.vocab_size

    def draft(active, tok, k):
        out = np.zeros((tok.shape[0], k), np.int32)
        for slot, req in active.items():
            ref = refs[req.rid % len(refs)]
            pos = len(req.tokens)          # next position to generate
            for i in range(k):
                t = ref[pos + i]
                if wrong_every and (pos + i) % wrong_every == 0:
                    t = (t + 1) % vocab
                out[slot, i] = t
        return out

    return draft, refs


class TestSpeculativeDecode:
    def test_branch_draft_bit_identical_both_pools(self, cell):
        """The headline invariant: spec mode with the REAL branch-only
        draft model (trunk_skip) returns exactly the non-speculative
        greedy tokens — mixed prompt lengths, staggered retirement,
        dense and paged pools."""
        model, _, params = cell
        gens = [4, 7, 3, 6, 5]
        for pool in (SlotPool(model, 2, MAX_LEN),
                     PagedPool(model, 4, 18, 8, MAX_LEN)):
            b = ContinuousBatcher(model, params, pool, spec_k=3)
            prompts = _prompts(5, model.cfg.vocab_size)
            reqs = [b.submit(p, g) for p, g in zip(prompts, gens)]
            b.drain(max_steps=500)
            for r, p, g in zip(reqs, prompts, gens):
                assert r.tokens == _solo_decode(model, params, p, g), \
                    f"request {r.rid} diverged ({type(pool).__name__})"
            assert pool.occupancy == 0
        assert b.spec_rounds > 0 and b.drafted_total > 0

    def test_partial_acceptance_parity_and_accounting(self, cell):
        """An oracle draft that misses every 3rd position still yields
        bit-identical output, and the drafted/matched counters add up."""
        model, _, params = cell
        prompts = _prompts(4, model.cfg.vocab_size, seed=5)
        gens = [6, 8, 5, 7]
        draft, refs = _oracle_draft(model, params, prompts, gens,
                                    wrong_every=3)
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, params, pool, spec_k=4,
                              draft_source=draft)
        reqs = [b.submit(p, g) for p, g in zip(prompts, gens)]
        b.drain(max_steps=500)
        for r, ref in zip(reqs, refs):
            assert r.tokens == ref
        assert 0.0 < b.acceptance_rate < 1.0
        assert b.drafted_total == sum(r.drafted for r in reqs)
        assert b.matched_total == sum(r.matched for r in reqs)
        for r in reqs:
            assert 0 <= r.matched <= r.drafted
            # every round lands >=1 token, so at most gen rounds of <=k
            assert r.drafted <= 4 * len(r.tokens)

    def test_rejected_drafts_never_leak_blocks(self, cell):
        """An always-wrong draft forces a full rollback every round;
        the paged pool's block accounting must still balance to zero
        and the output must still be exact (each round lands the one
        corrected token)."""
        model, _, params = cell
        prompts = _prompts(3, model.cfg.vocab_size, seed=2)
        gens = [5, 6, 4]
        draft, refs = _oracle_draft(model, params, prompts, gens,
                                    wrong_every=1)
        pool = PagedPool(model, 3, 18, 8, MAX_LEN)
        b = ContinuousBatcher(model, params, pool, spec_k=4,
                              draft_source=draft)
        reqs = [b.submit(p, g) for p, g in zip(prompts, gens)]
        high = 0
        while not b.idle:
            b.step()
            high = max(high, pool.blocks_in_use)
            assert b.step_count < 500
        for r, ref in zip(reqs, refs):
            assert r.tokens == ref
        assert b.acceptance_rate == 0.0
        assert high > 0
        assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0
        assert pool.occupancy == 0

    def test_midstream_scenario_swap_under_spec(self, cell):
        """A scenario swap queued while spec rounds are in flight must
        hold until the admitted requests finish, then requests admitted
        under the new branch must match ITS solo greedy decode — the
        draft shadow cache swaps along with the verify path."""
        from repro.scenario import swap_params
        model, _, pA = cell
        brB = jax.tree.map(
            lambda x: x + jnp.asarray(0.02, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            rebranch.partition(pA)[0])
        pB = swap_params(jax.tree.map(jnp.array, pA), brB)
        pool = SlotPool(model, 2, MAX_LEN)
        b = ContinuousBatcher(model, jax.tree.map(jnp.array, pA), pool,
                              scenario="a", spec_k=2)
        prompts = _prompts(2, model.cfg.vocab_size, seed=13)
        r1 = b.submit(prompts[0], 6, scenario="a")
        b.step()                                # spec round under A
        assert r1.admit_step >= 0 and not r1.done
        b.swap("b", brB)
        b.step()
        assert b.scenario == "a"                # barrier held
        r2 = b.submit(prompts[1], 5, scenario="b")
        b.drain(max_steps=200)
        assert b.scenario == "b" and b.swap_count == 1
        assert r1.tokens == _solo_decode(model, pA, prompts[0], 6)
        assert r2.tokens == _solo_decode(model, pB, prompts[1], 5)

    def test_verify_block_wider_than_horizon_raises(self, cell):
        model, _, params = cell
        cache = model.init_cache(2, 16, dtype=jnp.float32)
        with pytest.raises(ValueError, match="horizon"):
            model.verify_step(params,
                              jnp.zeros((2, 17), jnp.int32), cache)

    def test_trunk_skip_is_branch_only_math(self):
        """apply_linear under trunk_skip == the closed-form branch
        (x@C)@(core@U): no trunk contribution, no engine dispatch."""
        spec = rebranch.ReBranchSpec(d_ratio=2, u_ratio=2)
        key = jax.random.PRNGKey(3)
        p = rebranch.init_linear(key, 16, 12, spec, use_bias=True)
        p["sram"]["core"] = jax.random.normal(
            jax.random.PRNGKey(4), p["sram"]["core"].shape,
            p["sram"]["core"].dtype)
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 16))
        skip = dataclasses.replace(spec, trunk_skip=True)
        y = rebranch.apply_linear(p, x, skip)
        core_u = p["sram"]["core"].astype(x.dtype) @ p["rom"]["U"].astype(
            x.dtype)
        want = (x @ p["rom"]["C"].astype(x.dtype)) @ core_u \
            + p["sram"]["b"].astype(x.dtype)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # branchless ROM site: the draft contributes exactly zero
        solo = rebranch.ReBranchSpec(branch_enabled=False, trunk_skip=True)
        p2 = rebranch.init_linear(key, 16, 12,
                                  dataclasses.replace(
                                      solo, trunk_skip=False))
        np.testing.assert_array_equal(
            np.asarray(rebranch.apply_linear(p2, x, solo)),
            np.zeros((3, 12), np.float32))

    def test_draft_config_flips_every_enabled_site(self, cell):
        model, _, _ = cell
        cfg = model.cfg
        dcfg = api.draft_config(cfg)
        if cfg.rebranch.enabled:
            assert dcfg.rebranch.trunk_skip
        for _site, spec in dcfg.rebranch_overrides:
            if spec.enabled:
                assert spec.trunk_skip
        # idempotent: a draft of a draft is the same config
        assert api.draft_config(dcfg) == dcfg

    def test_spec_rejected_for_recurrent_families(self):
        cfg = configs.get_smoke("falcon_mamba_7b")
        assert not api.supports_speculation(cfg)
        model = deploy.compile_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pool = SlotPool(model, 1, 32)
        with pytest.raises(ValueError, match="spec_k=0"):
            ContinuousBatcher(model, params, pool, spec_k=2)
        with pytest.raises(ValueError, match="speculative verify"):
            cache = model.init_cache(1, 32, dtype=jnp.float32)
            model.verify_step(params, jnp.zeros((1, 2), jnp.int32), cache)

    def test_spec_k_validation(self, cell):
        model, _, params = cell
        with pytest.raises(ValueError, match="spec_k"):
            ContinuousBatcher(model, params, SlotPool(model, 1, MAX_LEN),
                              spec_k=-1)


# ---------------------------------------------------------------------------
# paged-pool rollback primitive (spec decode's undo path)
# ---------------------------------------------------------------------------

class TestPoolRollback:
    def test_prepare_tokens_grants_then_rollback_returns_tail(self, cell):
        model, _, params = cell
        pool = PagedPool(model, 2, 12, 8, MAX_LEN)
        cache = pool.solo_cache()
        prompt = _prompts(1, model.cfg.vocab_size)[0]   # 6 tokens
        _, cache = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(np.asarray(prompt)[None])},
            cache)
        row = pool.try_admit(prompt.size + 10)
        pool.adopt(row, cache)
        start_len = int(prompt.size)
        before = pool.blocks_in_use
        reserved = pool.blocks_reserved
        pool.prepare_tokens(4)               # room for a k=4 verify block
        grown = pool.blocks_in_use
        assert grown > before                # 6+4=10 spans block 2
        pool.rollback({row: start_len + 1})  # keep 1 accepted token
        assert pool.blocks_in_use == before  # tail block came back
        assert pool.blocks_reserved == reserved  # reservation re-credited
        assert pool._len[row] == start_len + 1
        # re-granting after a rollback reuses the freed tail blocks
        pool.prepare_tokens(4)
        assert pool.blocks_in_use == grown
        pool.release(row)
        assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0

    def test_rollback_validation(self, cell):
        model, _, _ = cell
        pool = PagedPool(model, 2, 12, 8, MAX_LEN)
        with pytest.raises(ValueError, match="holds no blocks"):
            pool.rollback({0: 5})            # row never admitted
        with pytest.raises(ValueError, match="at least one token"):
            pool.prepare_tokens(0)
        row = pool.try_admit(10)
        pool.prepare_tokens(3)
        with pytest.raises(ValueError, match="only ever truncates"):
            pool.rollback({row: 99})         # growth is not a rollback
        pool.release(row)


# ---------------------------------------------------------------------------
# registry LRU residency cap (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

class TestRegistryLRU:
    def _mini(self, name, size):
        serve.register(serve.ModelEntry(
            name, config=lambda: cnn.CNNConfig(name="vgg8",
                                               input_size=size)),
            override=True)

    def test_cap_evicts_oldest_and_hits_refresh_recency(self):
        for n, s in (("lru-a", 16), ("lru-b", 16), ("lru-c", 16)):
            self._mini(n, s)
        try:
            serve.set_max_resident(2)
            ma, _ = serve.compile_entry("lru-a")
            serve.compile_entry("lru-b")
            assert "lru-a" in serve.resident_ids()
            serve.compile_entry("lru-a")     # hit: a becomes most-recent
            serve.compile_entry("lru-c")     # evicts b, NOT a
            ids = serve.resident_ids()
            assert "lru-b" not in ids and "lru-a" in ids and "lru-c" in ids
            assert len(ids) <= 2
            ma2, _ = serve.compile_entry("lru-a")
            assert ma2 is ma                 # survivor kept its cell
        finally:
            serve.set_max_resident(None)
            for n in ("lru-a", "lru-b", "lru-c"):
                serve.evict(n)

    def test_evicted_id_recompiles_fresh(self):
        self._mini("lru-d", 16)
        m1, _ = serve.compile_entry("lru-d")
        assert serve.evict("lru-d")
        assert not serve.evict("lru-d")      # idempotent: already gone
        m2, _ = serve.compile_entry("lru-d")
        assert m2 is not m1
        serve.evict("lru-d")

    def test_cap_validation(self):
        with pytest.raises(ValueError, match="max_resident"):
            serve.set_max_resident(0)
        assert serve.max_resident() is None

"""Speculative decode race: branch-draft + batched verify vs plain decode.

Drives the ``repro.serve`` continuous batcher over one resident compiled
cell in speculative mode (``spec_k > 0``: up to k tokens per row drafted
by the branch-only model — ROM trunks skipped — then ONE batched
``verify_step`` through the full trunk+branch cell per round) and races
it against the same load with speculation off.  Because acceptance rate
is the whole story for speculative decode, the benchmark sweeps it
deterministically: an ORACLE draft source proposes the known greedy
continuation with probability alpha per position (seeded per request and
position), so the acceptance axis is dialed, not hoped for; one row also
runs the real branch drafter, whose acceptance is a measured property of
the ReBranch approximation itself.

Reported per configuration:

  * aggregate decode tokens/s and the spec-on/spec-off ratio — the
    headline: at high acceptance, k tokens land per full-cell dispatch
    instead of one;
  * per-request tokens/s (p50 over requests) alongside the aggregate,
    so batching effects and speculation effects stay distinguishable;
  * acceptance rate (accepted / verified draft tokens) and verify
    rounds vs plain decode steps;
  * drafted-vs-verified FLOP ratio from the placement plan's MAC stats
    ((branch + sram MACs) / total MACs — the ~1/16 asymmetry that makes
    the branch a nearly-free drafter);
  * two hard invariants, each exit-1 on violation: every configuration's
    output is BIT-IDENTICAL to the non-speculative greedy decode of the
    same prompts, and the paged pool's block accounting drains to zero
    (granted + reserved == 0) after every speculative run — rejected
    drafts must never leak blocks.

Prints CSV rows (``name,us_per_call,derived``) and doubles as the
``spec_decode`` section of ``benchmarks.run --json``.  Ratio/acceptance
rows carry 0 in the us field and names the CI gate recognises as
dimensionless (``benchmarks.compare.is_ratio_metric``).

  PYTHONPATH=src python -m benchmarks.spec_decode [--fast] [--users 6]
      [--gen 24] [--spec-k 4] [--alphas 0.6 0.95]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def _make_load(users: int, vocab: int, gen: int, seed: int = 0,
               prompt_min: int = 6, prompt_max: int = 24):
    """Deterministic mixed-length prompts (seeded content)."""
    rng = np.random.default_rng(seed)
    lens = np.linspace(prompt_min, prompt_max, users).astype(int)
    rng.shuffle(lens)
    return [rng.integers(1, vocab, size=int(n), dtype=np.int64)
            for n in lens], [gen] * users


def _solo_greedy(model, params, prompts, gens, max_len: int) -> list:
    """The greedy continuation per prompt — the bit-parity reference
    AND the oracle drafter's answer sheet."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    out = []
    for p, g in zip(prompts, gens):
        cache = model.init_cache(1, max_len, dtype=jnp.float32)
        logits, cache = prefill(params, {"tokens": jnp.asarray(p[None])},
                                cache)
        tok = int(jnp.argmax(logits[0, -1]))
        toks = [tok]
        for _ in range(g - 1):
            logits, cache = decode(
                params, jnp.asarray([[tok]], jnp.int32), cache)
            tok = int(jnp.argmax(logits[0, -1]))
            toks.append(tok)
        out.append(toks)
    return out


def _oracle(refs: list, vocab: int, alpha: float, seed: int = 0):
    """A draft source proposing the known greedy continuation with
    probability ``alpha`` per position (else a deliberately wrong
    token), seeded per (request, position): the acceptance rate is a
    dial, and reruns are deterministic.  Greedy accept-longest-prefix
    cuts the round at the first wrong draft, so the EXPECTED accepted
    run per round is the geometric partial sum of alpha."""
    coins = [np.random.default_rng((seed, rid)).random(len(ref))
             for rid, ref in enumerate(refs)]

    def draft(active, last_tok, k):
        drafts = np.zeros((last_tok.shape[0], k), np.int32)
        for slot, req in active.items():
            # rids run on across races of the same load (warm pass then
            # timed pass); submission order maps them back to prompts
            idx = req.rid % len(refs)
            ref, coin = refs[idx], coins[idx]
            pos = len(req.tokens)
            for i in range(k):
                true_tok = ref[pos + i]
                drafts[slot, i] = true_tok if coin[pos + i] < alpha \
                    else (true_tok + 1) % vocab
        return drafts

    return draft


def _race(srv, prompts, gens):
    """Submit everything, drain, time.  Returns (requests, wall_s)."""
    t0 = time.perf_counter()
    reqs = [srv.submit(p, g) for p, g in zip(prompts, gens)]
    srv.drain(max_steps=200_000)
    return reqs, time.perf_counter() - t0


def simulate(model_id: str = "gemma-2b-smoke", *, users: int = 6,
             gen: int = 24, slots: int = 4, spec_k: int = 4,
             alpha: float | None = None, draft: str = "oracle",
             paged: bool = True, max_len: int = 64, block_size: int = 8,
             seed: int = 0, shared: dict | None = None) -> dict:
    """One speculative (or plain, ``spec_k=0``) serving run.

    draft='oracle' uses the alpha-dialed oracle draft source (requires
    ``alpha``); draft='branch' runs the real branch-only draft model.
    ``shared`` carries (model, params, prompts, gens, solo tokens)
    across configurations so every run races the identical load on the
    identical cell.
    """
    from repro import serve

    if shared is None:
        model, plan = serve.compile_entry(model_id)
        params = model.init(jax.random.PRNGKey(seed))
        prompts, gens = _make_load(users, model.cfg.vocab_size, gen, seed)
        for p in prompts:
            if p.size + gen > max_len:
                raise ValueError(f"prompt {p.size} + gen {gen} exceeds "
                                 f"max_len {max_len}")
        solo = _solo_greedy(model, params, prompts, gens, max_len)
        shared = {"model": model, "plan": plan, "params": params,
                  "prompts": prompts, "gens": gens, "solo": solo}
    model, params = shared["model"], shared["params"]
    prompts, gens, solo = shared["prompts"], shared["gens"], shared["solo"]

    draft_source = None
    if spec_k and draft == "oracle":
        if alpha is None:
            raise ValueError("draft='oracle' needs alpha")
        draft_source = _oracle(solo, model.cfg.vocab_size, alpha, seed)

    srv = serve.LMServer(
        model, params, n_slots=slots, max_len=max_len, paged=paged,
        block_size=block_size if paged else None,
        spec_k=spec_k, draft_source=draft_source)
    # warm pass on the SAME server (its jit wrappers hold the trace
    # caches): the load drains completely, so the pool is clean and the
    # timed pass measures scheduling + execution, not compilation
    _race(srv, prompts, gens)
    b = srv.batcher
    steps0, rounds0 = b.step_count, b.spec_rounds
    drafted0, matched0 = b.drafted_total, b.matched_total
    reqs, wall = _race(srv, prompts, gens)

    total = sum(len(r.tokens) for r in reqs)
    per_req = sorted(len(r.tokens) / max(r.latency_s, 1e-9) for r in reqs)
    leak = 0
    if paged:
        leak = srv.pool.blocks_in_use + srv.pool.blocks_reserved
    return {
        "spec_k": spec_k, "draft": draft if spec_k else "off",
        "alpha": alpha, "users": users, "gen": gen, "paged": paged,
        "total_tokens": total, "wall_s": wall,
        "tokens_s": total / wall,
        "tokens_s_p50_request": per_req[len(per_req) // 2],
        "steps": b.step_count - steps0,
        "spec_rounds": b.spec_rounds - rounds0,
        "drafted": b.drafted_total - drafted0,
        "acceptance": ((b.matched_total - matched0)
                       / max(1, b.drafted_total - drafted0)
                       if spec_k else 0.0),
        "bit_identical": all(list(r.tokens) == s
                             for r, s in zip(reqs, solo)),
        "leaked_blocks": leak,
        "shared": shared,
    }


def flop_ratio(shared: dict) -> float:
    """(branch + SRAM MACs) / total MACs per token under the resident
    plan — what one draft token costs relative to one verify token."""
    plan = shared.get("plan")
    if plan is None:
        return float("nan")
    stats = plan.stats(shared["model"].cfg)
    return (stats.branch_macs + stats.sram_macs) / max(1, stats.total_macs)


def report_lines(results: list, base: dict, shared: dict) -> list[str]:
    """CSV rows for benchmarks.run.  Wall-us rows feed the CI latency
    gate; ratio/acceptance rows carry 0 us and ratio-marked names."""
    lines = [
        f"spec_us_per_token_off,"
        f"{base['wall_s'] * 1e6 / base['total_tokens']:.0f},"
        f"tokens_s={base['tokens_s']:.1f} "
        f"p50_req_tokens_s={base['tokens_s_p50_request']:.1f} "
        f"steps={base['steps']} bit_identical={base['bit_identical']}",
    ]
    for r in results:
        tag = (f"{r['draft']}_a{int(r['alpha'] * 100)}"
               if r["draft"] == "oracle" else r["draft"])
        lines += [
            f"spec_us_per_token_{tag},"
            f"{r['wall_s'] * 1e6 / r['total_tokens']:.0f},"
            f"tokens_s={r['tokens_s']:.1f} "
            f"p50_req_tokens_s={r['tokens_s_p50_request']:.1f} "
            f"rounds={r['spec_rounds']} k={r['spec_k']} "
            f"bit_identical={r['bit_identical']} "
            f"leaked_blocks={r['leaked_blocks']}",
            f"spec_acceptance_{tag},0,"
            f"acceptance={r['acceptance']:.3f} drafted={r['drafted']}",
            f"spec_speedup_ratio_{tag},0,"
            f"tokens_s_ratio={r['tokens_s'] / base['tokens_s']:.2f} "
            f"p50_req_ratio="
            f"{r['tokens_s_p50_request'] / base['tokens_s_p50_request']:.2f}",
        ]
    lines.append(f"spec_flop_ratio_draft_vs_verify,0,"
                 f"ratio={flop_ratio(shared):.4f}")
    return lines


def run() -> list[str]:
    """benchmarks.run section: spec-off baseline, oracle acceptance at
    0.6 and 0.95, and the real branch drafter, all over the paged pool
    (the rollback-accounting path).  bit_identical and leaked_blocks
    ride in the derived column of every BENCH_*.json."""
    base = simulate(spec_k=0)
    shared = base["shared"]
    results = [
        simulate(spec_k=4, alpha=0.6, draft="oracle", shared=shared),
        simulate(spec_k=4, alpha=0.95, draft="oracle", shared=shared),
        simulate(spec_k=4, draft="branch", shared=shared),
    ]
    return report_lines(results, base, shared)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small load (CI smoke): 4 users, 12 tokens")
    ap.add_argument("--users", type=int, default=6)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--alphas", nargs="+", type=float, default=[0.6, 0.95])
    ap.add_argument("--dense", action="store_true",
                    help="dense SlotPool instead of the paged pool")
    ap.add_argument("--model", default="gemma-2b-smoke")
    args = ap.parse_args(argv)
    users, gen = args.users, args.gen
    if args.fast:
        users, gen = min(users, 4), min(gen, 12)

    kw = dict(users=users, gen=gen, slots=args.slots,
              paged=not args.dense)
    base = simulate(args.model, spec_k=0, **kw)
    shared = base["shared"]
    results = [simulate(args.model, spec_k=args.spec_k, alpha=a,
                        draft="oracle", shared=shared, **kw)
               for a in args.alphas]
    results.append(simulate(args.model, spec_k=args.spec_k,
                            draft="branch", shared=shared, **kw))

    print("name,us_per_call,derived")
    for line in report_lines(results, base, shared):
        print(line)

    ok = True
    for r in [base] + results:
        tag = f"{r['draft']} alpha={r['alpha']}"
        if not r["bit_identical"]:
            print(f"FAIL: {tag} diverged from non-speculative greedy "
                  f"decode (speculation must be bit-neutral)")
            ok = False
        if r["leaked_blocks"]:
            print(f"FAIL: {tag} leaked {r['leaked_blocks']} pool blocks "
                  f"after drain (rollback accounting broken)")
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())

"""Serving load generator: paged vs dense pools under mixed-length load.

Drives the `repro.serve` continuous batcher with a deterministic
mixed-prompt-length load (seeded content, lengths spread over
[--prompt-min, --prompt-max], fixed arrival schedule: user i submits
after i ``--stagger`` decode ticks) against one resident compiled cell,
TWICE — once over the dense ``SlotPool`` and once over a ``PagedPool``
carved from the SAME byte budget — then replays the SAME prompts
through the solo prefill+decode path both pools must stay bit-identical
to.  Reports:

  * aggregate decode throughput (tokens/s) for all three paths and the
    paged/dense/solo ratios — the paper's "weights never move" premise
    as a serving number: one ROM cell amortized across concurrent
    users, and the plan-budgeted KV bytes amortized across mixed
    request lengths;
  * per-request wall latency p50/p99 with each request's PROMPT LENGTH
    alongside, so the mixed-length distribution is visible in the
    ``BENCH_*.json`` record;
  * pool utilization / fragmentation: live KV tokens over committed
    capacity (granted blocks for paged, whole occupied rows for dense)
    sampled every decode tick — the number paging exists to raise.

Prints CSV rows (``name,us_per_call,derived``) and doubles as the
``serve_load`` section of ``benchmarks.run --json``, so the CI gate
(`benchmarks.compare`) regression-checks the serve path like any
kernel row.

  PYTHONPATH=src python -m benchmarks.serve_load [--fast] [--users 8]
      [--gen 16] [--slots 4] [--stagger 1] [--prompt-min 8]
      [--prompt-max 128]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def _make_load(users: int, vocab: int, gen: int, seed: int = 0,
               prompt_min: int = 8, prompt_max: int = 128):
    """Deterministic per-user prompts: lengths spread evenly over
    [prompt_min, prompt_max], shuffled, seeded content."""
    rng = np.random.default_rng(seed)
    lens = np.linspace(prompt_min, prompt_max, users).astype(int)
    rng.shuffle(lens)
    return [rng.integers(0, vocab, size=int(n), dtype=np.int64)
            for n in lens], [gen] * users


def _solo_replay(model, params, prompts, gens, max_len: int) -> dict:
    """The baseline every pool must match bitwise: sequential batch=1
    prefill + decode per prompt (traces warmed first, so the timed pass
    measures execution, not compile caches)."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    for p in {p.size: p for p in prompts}.values():
        c = model.init_cache(1, max_len, dtype=jnp.float32)
        lg, c = prefill(params, {"tokens": jnp.asarray(p[None])}, c)
        lg, c = decode(params, jnp.asarray([[0]], jnp.int32), c)
        jax.block_until_ready(lg)
    tokens = []
    t0 = time.perf_counter()
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
        tokens.append(toks)
    return {"tokens": tokens, "wall_s": time.perf_counter() - t0}


def _race(srv, prompts, gens, stagger: int):
    """Submit the load on its arrival schedule and drain; returns
    (requests, wall_s, decode_steps, mean_utilization, peak_active)."""
    batcher = srv.batcher
    step0 = batcher.step_count
    reqs, util, peak = [], [], 0
    t0 = time.perf_counter()
    tick = 0
    while len(reqs) < len(prompts) or not batcher.idle:
        while len(reqs) < len(prompts) and len(reqs) * stagger <= tick:
            i = len(reqs)
            reqs.append(srv.submit(prompts[i], gens[i]))
        srv.step()
        # live KV tokens over committed capacity: granted blocks for
        # the paged pool, whole occupied rows for the dense one
        live = sum(r.prompt.size + len(r.tokens)
                   for r in batcher._active.values())
        pool = srv.pool
        committed = (pool.blocks_in_use * pool.block_size
                     if hasattr(pool, "blocks_in_use")
                     else pool.occupancy * pool.max_len)
        if committed:
            util.append(min(1.0, live / committed))
        peak = max(peak, batcher.active)
        tick += 1
        if tick > 100_000:
            raise RuntimeError("load loop stuck")
    wall = time.perf_counter() - t0
    return (reqs, wall, batcher.step_count - step0,
            float(np.mean(util)) if util else 0.0, peak)


def simulate(model_id: str = "gemma-2b-smoke", *, users: int = 8,
             gen: int = 16, slots: int = 4, stagger: int = 1,
             max_len: int = 160, seed: int = 0, paged: bool = False,
             prompt_min: int = 8, prompt_max: int = 128,
             block_size: int = 16, prefill_chunk: int | None = None,
             solo: dict | None = None) -> dict:
    """One batched run + one solo replay; returns the report dict.

    ``paged=True`` serves the same load through a :class:`PagedPool`
    sized to the SAME byte budget as ``slots`` dense rows
    (``slots * max_len / block_size`` blocks) but twice the batch rows,
    so the fragmentation win shows up as admitted concurrency.  Pass
    ``solo=`` (a previous run's ``["solo"]``) to skip re-timing the
    solo replay when racing both pools over one load.
    """
    from repro import serve

    model, _plan = serve.compile_entry(model_id)
    params = model.init(jax.random.PRNGKey(seed))
    prompts, gens = _make_load(users, model.cfg.vocab_size, gen, seed,
                               prompt_min, prompt_max)
    for p in prompts:
        if p.size + gen > max_len:
            raise ValueError(
                f"prompt {p.size} + gen {gen} exceeds max_len {max_len}")

    if paged:
        rows = 2 * slots
        n_blocks = slots * (max_len // block_size)
        srv = serve.LMServer(model, params, n_slots=rows, max_len=max_len,
                             paged=True, block_size=block_size,
                             n_blocks=n_blocks,
                             prefill_chunk=prefill_chunk)
    else:
        rows, n_blocks = slots, 0
        srv = serve.LMServer(model, params, n_slots=slots, max_len=max_len,
                             paged=False, prefill_chunk=prefill_chunk)

    # warm pass: the same load once through (compiles every prefill
    # bucket — including chunked-prefill shapes — and the decode step),
    # so the timed race below measures scheduling, not compile caches
    _race(srv, prompts, gens, stagger)
    reqs, wall_b, n_steps, mean_util, peak = _race(srv, prompts, gens,
                                                   stagger)
    total_tokens = sum(len(r.tokens) for r in reqs)
    lats = sorted(r.latency_s for r in reqs)
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(np.ceil(0.99 * len(lats))) - 1)]

    if solo is None:
        solo = _solo_replay(model, params, prompts, gens, max_len)
    bitwise = all(list(r.tokens) == s
                  for r, s in zip(reqs, solo["tokens"]))
    return {
        "model_id": model_id, "users": users, "gen": gen,
        "paged": paged, "rows": rows, "slots": slots,
        "n_blocks": n_blocks, "block_size": block_size if paged else 0,
        "total_tokens": total_tokens, "decode_steps": n_steps,
        "wall_batched_s": wall_b, "wall_solo_s": solo["wall_s"],
        "tokens_s_batched": total_tokens / wall_b,
        "tokens_s_solo": total_tokens / solo["wall_s"],
        "speedup": solo["wall_s"] / wall_b,
        "p50_ms": p50 * 1e3, "p99_ms": p99 * 1e3,
        "mean_utilization": mean_util,
        "fragmentation": 1.0 - mean_util,
        "peak_active": peak,
        "per_request": [
            {"prompt_len": int(r.prompt.size),
             "latency_ms": r.latency_s * 1e3} for r in reqs],
        "bit_identical": bitwise,
        "solo": solo,
    }


def report_lines(r: dict, tag: str) -> list[str]:
    """CSV rows for benchmarks.run; wall_us rows feed the CI gate.

    The latency row carries every request's prompt length alongside
    p50/p99 (``len:latency`` pairs), so the mixed-length distribution
    is recorded in BENCH_*.json, not just its aggregates.
    """
    us_per_tok = r["wall_batched_s"] * 1e6 / r["total_tokens"]
    n = f"{r['users']}u"
    per_req = "|".join(f"{d['prompt_len']}:{d['latency_ms']:.0f}ms"
                       for d in r["per_request"])
    return [
        f"serve_us_per_token_{tag}_{n},{us_per_tok:.0f},"
        f"tokens_s={r['tokens_s_batched']:.1f} speedup="
        f"{r['speedup']:.2f}x bit_identical={r['bit_identical']}",
        f"serve_latency_{tag}_{n},0,p50_ms={r['p50_ms']:.1f} "
        f"p99_ms={r['p99_ms']:.1f} decode_steps={r['decode_steps']} "
        f"prompt_ms={per_req}",
        f"serve_pool_{tag}_{n},0,utilization="
        f"{r['mean_utilization']:.3f} fragmentation="
        f"{r['fragmentation']:.3f} peak_active={r['peak_active']} "
        f"rows={r['rows']}",
    ]


def run() -> list[str]:
    """benchmarks.run section: the acceptance geometry — 8 users at
    mixed prompt lengths 8..128 over (a) a 4-slot dense pool and (b) a
    paged pool of the same byte budget — plus the solo reference row.
    bit_identical rides along in the derived column so a parity break
    is visible in every BENCH_*.json."""
    dense = simulate(users=8, gen=16, slots=4, paged=False)
    paged = simulate(users=8, gen=16, slots=4, paged=True,
                     solo=dense["solo"])
    us_solo = dense["wall_solo_s"] * 1e6 / dense["total_tokens"]
    return (report_lines(dense, "dense")
            + report_lines(paged, "paged")
            + [f"serve_us_per_token_solo_8u,{us_solo:.0f},"
               f"tokens_s={dense['tokens_s_solo']:.1f}",
               f"serve_paged_vs_dense_8u,0,tokens_s_ratio="
               f"{paged['tokens_s_batched'] / dense['tokens_s_batched']:.2f}"
               f" util_ratio={paged['mean_utilization'] / max(1e-9, dense['mean_utilization']):.2f}"
               f" peak_active={paged['peak_active']}v{dense['peak_active']}"])


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small load (CI smoke): 4 users, 6 tokens, "
                         "prompts to 64")
    ap.add_argument("--users", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stagger", type=int, default=1)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=160)
    ap.add_argument("--model", default="gemma-2b-smoke")
    args = ap.parse_args(argv)
    if args.fast:
        args.users, args.gen = min(args.users, 4), min(args.gen, 6)
        args.prompt_max = min(args.prompt_max, 64)
        args.max_len = min(args.max_len, 96)
    kw = dict(users=args.users, gen=args.gen, slots=args.slots,
              stagger=args.stagger, prompt_min=args.prompt_min,
              prompt_max=args.prompt_max, max_len=args.max_len)
    dense = simulate(args.model, paged=False, **kw)
    paged = simulate(args.model, paged=True, solo=dense["solo"], **kw)
    print("name,us_per_call,derived")
    for line in (report_lines(dense, "dense")
                 + report_lines(paged, "paged")):
        print(line)
    ok = True
    for r, tag in ((dense, "dense"), (paged, "paged")):
        if not r["bit_identical"]:
            print(f"FAIL: {tag} serve output diverged from the solo path")
            ok = False
    if paged["peak_active"] < dense["peak_active"] or \
            paged["mean_utilization"] < dense["mean_utilization"] * 0.5:
        print("WARN: paged pool shows no occupancy/utilization win "
              "over dense at this load")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())

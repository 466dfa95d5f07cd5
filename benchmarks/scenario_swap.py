"""Scenario hot-swap benchmark: K branches over ONE resident ROM trunk.

The tentpole claim of the scenario subsystem (`repro.scenario`): once a
trunk is resident, switching the chip to another dataset/task is a
branch swap — one donated combine over the fixed ROM image — not a
model reload.  This benchmark makes that a measured number:

  1. pretrain a VGG-8 on synthetic task A and tape it out to ROM
     (``transfer_harness``, the Fig. 10 flow);
  2. train K distinct ReBranch-only scenarios on the SAME trunk
     (one synthetic transfer target each);
  3. register them with the serving layer and race
        branch hot-swap  (``CNNServer.swap_scenario``: donated combine,
                          resident jit executable reused)
     against
        full reload      (``registry.evict`` + ``compile_entry`` +
                          fresh jit forward — what serving a new
                          scenario costs WITHOUT the subsystem);
  4. verify the correctness bar: a hot-swapped branch is bit-identical
     to a freshly compiled single-scenario cell, and each scenario's
     eval accuracy through the serve path matches the direct path.

Emits ``name,us_per_call,derived`` CSV rows (``--json`` for records);
wired into ``benchmarks.run`` and gated by ``benchmarks.compare``.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import transfer_harness as th
from repro import deploy, scenario, serve
from repro import plan as plan_lib
from repro.core import rebranch
from repro.core.rebranch import ReBranchSpec
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cnn

MODEL_ID = "vgg8-swap-bench"


def _fresh(params):
    """A deep copy — keeps a reference tree alive across donated swaps."""
    return jax.tree.map(lambda x: jnp.array(x), params)


def _accuracy_from(predict, tc, seed):
    correct = total = 0
    for i in range(tc.eval_batches):
        x, y = synthetic.image_batch(seed, 10_000 + i, tc.batch,
                                     tc.input_size, tc.num_classes)
        pred = np.argmax(predict(x), axis=-1)
        correct += int(np.sum(pred == np.asarray(y)))
        total += tc.batch
    return correct / total


def simulate(k: int = 2, tc: th.TransferConfig | None = None,
             swap_reps: int = 10) -> dict:
    """Train K scenario branches on one trunk, then measure swap vs
    reload latency and per-scenario serve/direct accuracy parity."""
    tc = tc or th.TransferConfig()
    dense, _ = th.pretrained_dense(tc)
    spec = ReBranchSpec()
    cfg = th.small_vgg_cfg(spec, tc)
    plan = plan_lib.PlacementPlan.from_config(cfg)
    frozen = cnn.freeze_to_rom(dense, jax.random.PRNGKey(7), spec)

    # -- K scenarios: branch-only transfer to K distinct tasks ----------
    model = deploy.compile_model(cfg, plan=plan)
    names, bundles, seeds, acc_direct = [], {}, {}, {}
    for i in range(k):
        name = f"task{i}"
        seed = tc.seed_b + 1000 * i
        p_i = th._train(_fresh(frozen), model.cfg, tc, seed,
                        tc.finetune_steps)
        bundles[name] = scenario.extract(model, p_i, plan)
        acc_direct[name] = _accuracy_from(
            lambda x: np.asarray(model.forward(p_i, x)), tc, seed)
        names.append(name)
        seeds[name] = seed

    # -- serve them all from one resident cell --------------------------
    serve.register(serve.ModelEntry(
        MODEL_ID, config=lambda: cfg, plan=lambda c: plan), override=True)
    store = serve.scenario_store(MODEL_ID, capacity=max(2, k))
    for name in names:
        store.register(name, bundle=bundles[name], override=True)
    srv = serve.load(MODEL_ID, params=_fresh(frozen), n_slots=tc.batch,
                     scenario=names[0])
    xw, _ = synthetic.image_batch(tc.seed_b, 10_000, tc.batch,
                                  tc.input_size, tc.num_classes)
    srv.submit(xw)                                   # warm the jit cell

    # -- swap latency: donated combine + resident executable ------------
    swap_times = []
    for r in range(swap_reps):
        target = names[(r + 1) % len(names)]
        t0 = time.perf_counter()
        srv.swap_scenario(target)
        jax.block_until_ready(srv.params)
        swap_times.append(time.perf_counter() - t0)
    swap_us = float(np.median(swap_times) * 1e6)

    # -- full reload: what the swap replaces ----------------------------
    reload_times = []
    for _ in range(2):
        serve.evict(MODEL_ID)
        t0 = time.perf_counter()
        srv2 = serve.load(MODEL_ID, params=_fresh(frozen), n_slots=tc.batch)
        np.asarray(srv2.submit(xw))                  # fresh jit compile
        reload_times.append(time.perf_counter() - t0)
    reload_us = float(min(reload_times) * 1e6)
    store = serve.scenario_store(MODEL_ID, capacity=max(2, k))
    for name in names:
        store.register(name, bundle=bundles[name], override=True)
    srv = serve.load(MODEL_ID, params=_fresh(frozen), n_slots=tc.batch)
    srv.submit(xw)

    # -- correctness bar: bitwise vs a freshly compiled cell ------------
    trunk = rebranch.partition(frozen)[1]
    acc_serve, parity = {}, {}
    for name in names:
        srv.swap_scenario(name)
        got = np.asarray(srv.submit(xw))
        fresh_model = deploy.compile_model(cfg, plan=plan)
        p_fresh = rebranch.combine(bundles[name].params, trunk)
        want = np.asarray(jax.jit(fresh_model.forward)(p_fresh,
                                                       jnp.asarray(xw)))
        parity[name] = bool(np.array_equal(got, want))
        acc_serve[name] = _accuracy_from(
            lambda x: np.asarray(srv.submit(x)), tc, seeds[name])
    return {
        "k": k, "swap_us": swap_us, "reload_us": reload_us,
        "speedup": reload_us / swap_us,
        "bit_identical": all(parity.values()),
        "parity": parity, "acc_serve": acc_serve,
        "acc_direct": acc_direct,
        "cache": {"hits": store.hits, "misses": store.misses,
                  "evicted": list(store.evicted)},
    }


def report_lines(r: dict) -> list[str]:
    """CSV rows for benchmarks.run; wall_us rows feed the CI gate."""
    lines = [
        f"scenario_swap_us,{r['swap_us']:.0f},"
        f"k={r['k']} speedup={r['speedup']:.1f}x_vs_reload "
        f"bit_identical={r['bit_identical']}",
        f"scenario_full_reload_us,{r['reload_us']:.0f},"
        f"compile_entry+jit_warm (the cost a hot-swap replaces)",
        f"scenario_swap_speedup,0,{r['speedup']:.1f}x "
        f"(acceptance: >=5x)",
    ]
    for name in sorted(r["acc_serve"]):
        lines.append(
            f"scenario_acc_{name},0,serve={r['acc_serve'][name]:.4f} "
            f"direct={r['acc_direct'][name]:.4f} "
            f"parity={r['parity'][name]}")
    return lines


def run() -> list[str]:
    """benchmarks.run section: 3 scenarios on one trunk, reduced
    training budget (the accuracy rows are parity checks, not Fig. 10
    reproductions — fig10_generalization owns the headline accuracy)."""
    tc = th.TransferConfig(pretrain_steps=80, finetune_steps=80,
                           eval_batches=4)
    return report_lines(simulate(k=3, tc=tc))


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: 2 scenarios, short training")
    ap.add_argument("--k", type=int, default=3,
                    help="number of scenario branches to train")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the result record as JSON")
    args = ap.parse_args(argv)
    if args.fast:
        tc = th.TransferConfig(pretrain_steps=40, finetune_steps=40,
                               eval_batches=2)
        args.k = min(args.k, 2)
    else:
        tc = th.TransferConfig(pretrain_steps=80, finetune_steps=80,
                               eval_batches=4)
    r = simulate(k=args.k, tc=tc)
    print("name,us_per_call,derived")
    for line in report_lines(r):
        print(line)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(r, f, indent=1)
    if not r["bit_identical"]:
        print("FAIL: hot-swapped branch diverged from a freshly "
              "compiled single-scenario cell")
        return 1
    if r["speedup"] < 5.0:
        print(f"FAIL: swap only {r['speedup']:.1f}x faster than a full "
              f"reload (acceptance: >=5x)")
        return 1
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

"""Bring-up smoke run of the serving path on a TPU, through the entry
points a user calls (``serve.register`` -> ``serve.load`` -> ``submit``).

Phases, all in this one process (it holds the chip; no child needs it):

  cnn  DarkNet-19 at 416 px, the paper's detection model, registered on
       the ``pallas_fused`` engine with its minimum-area ROM/SRAM plan and
       served by ``CNNServer.submit`` on two seeded batches of 8 images.
       Checked against the same params on the ``int8_native`` engine
       (``CNN_RTOL``); the compiled forward must hold Pallas kernels
       (``tpu_custom_call``).
  lm   qwen2-vl-2b (text path) at its published widths on a paged KV pool:
       4 seeded prompts of 32-128 tokens, 16 new tokens each, checked
       against solo prefill+decode of the same prompts on the params tree
       as given, without the branch the server derives for serving
       (greedy tokens agree, or the two candidates are a near-tie within
       ``LM_TIE_RTOL``).

Every phase serves seeded non-zero ReBranch cores (``with_branches``):
the init's zero cores would make each branch add exactly 0, and a kernel
that dropped or garbled it would still pass.

With ``--chips 4`` only the four-chip phase runs: DarkNet-19 at 416 px on
the ``pallas_sharded`` halo-exchange engine over ``make_cnn_serve_mesh(4)``,
checked against the one-chip ``pallas_fused`` output of the same images,
with zero branch cores (the trunk alone, ``SHARDED_RTOL``) and with
seeded ones (``CNN_RTOL``).

Times are single runs, printed for bring-up only; no peak rate is used.
The last stdout line is ``{"ok": true, "device": {...}}``; any failed
check or phase raises and exits non-zero without it, as does a run that
finds no TPU.

    python3 chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs, deploy, serve  # noqa: E402
from repro import plan as plan_lib  # noqa: E402
from repro.configs import paper_models  # noqa: E402
from repro.core import rebranch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_cnn_serve_mesh  # noqa: E402
from repro.models import cnn  # noqa: E402

# the params are drawn from SEED, the images from SEED + 1 and the
# branch cores from SEED + 2
SEED = 0
# A deployed branch is trained, not zero: each ReBranch core is drawn
# He-scaled (std sqrt(2 / fan_in)) times BRANCH_SCALE, so every branch
# moves its layer's output by about that share of the trunk's.
BRANCH_SCALE = 0.3
CONV_FAN_IN = (0, 1, 2)        # core [KH, KW, C_c, C_u]
LM_FAN_IN = (-2,)              # core [layers, d_c, d_u]

CNN_ID = "darknet19-416"
CNN_BATCH = 8
# ||pallas_fused - int8_native|| / ||int8_native|| over the whole output.
# The engines quantise activations at different granularity (per patch
# row and 512-wide k-block vs per whole patch row), so int8 rounding
# differs on every conv wider than 512 and compounds over 23 convs: on
# CPU at 64 and 128 px this reads 0.041-0.051, the same as int8_native
# vs the float 'dequant' engine.  A wrong kernel reads near 1 or more.
CNN_RTOL = 0.15
# With zero branch cores the pallas_sharded trunk is bit-identical to the
# unsharded kernel and the XLA pools repartition under GSPMD (same bound
# as tests/test_sharded_conv.py's whole-model check).  Max-abs err over
# max |ref|.  With seeded cores the branch sums in another order (one
# fused GEMM vs three XLA convs); int8 re-quantisation of each next
# input turns such float differences into one-step flips that compound
# over the layers, so that comparison is held to CNN_RTOL (on 4 CPU
# devices at 64 px it reads 0.028, with the trunk alone 0.0).
SHARDED_RTOL = 2e-4
# one halo exchange: collective-permute (async on TPU: its -start op)
_PERMUTE = re.compile(r"collective-permute(?:-start)?\(")

LM_ID = "qwen2-vl-2b"
LM_ROWS, LM_MAX_LEN, LM_BLOCK = 8, 1024, 16
LM_N_BLOCKS = LM_ROWS * LM_MAX_LEN // LM_BLOCK
LM_REQUESTS, LM_NEW_TOKENS = 4, 16
LM_PROMPT_LEN = (32, 128)
# A served token may differ from the solo reference's greedy token only
# when the reference scores the two within this fraction of the row's
# largest |logit| (bfloat16 keeps 8 significant bits: 7.8e-3 per step).
LM_TIE_RTOL = 2e-2


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def _rel_err(got, want) -> tuple[float, float]:
    """(max |got - want| / max |want|, ||got - want|| / ||want||)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
            float(np.linalg.norm(got - want) / np.linalg.norm(want)))


def _branch_effect(bare, seeded) -> float:
    """rel L2 distance of the seeded-branch output from the zero-branch
    one: about the error a kernel that dropped the branch would show."""
    _, effect = _rel_err(bare, seeded)
    _check(effect >= 2 * CNN_RTOL,
           f"the branches move the output by rel L2 {effect} only; a "
           f"dropped branch would pass CNN_RTOL {CNN_RTOL}")
    return effect


def _images(seed: int, n: int, cfg) -> jax.Array:
    return jax.random.uniform(jax.random.PRNGKey(seed),
                              (n, cfg.input_size, cfg.input_size, 3))


def with_branches(params, seed: int, fan_in_axes):
    """``params`` with every ReBranch ``core`` leaf drawn from ``seed``
    (see BRANCH_SCALE); every other leaf is passed through."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for key, (path, leaf) in zip(keys, flat):
        if getattr(path[-1], "key", None) == "core":
            fan_in = math.prod(leaf.shape[a] for a in fan_in_axes)
            leaf = jax.random.normal(key, leaf.shape, leaf.dtype) * (
                BRANCH_SCALE * math.sqrt(2.0 / fan_in))
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _param_gb(params) -> dict:
    """GB of params: all, the int8 trunk (``w_q``), the float ROM
    projections (``C``/``U``), the branch derived for serving."""
    gb = {"all": 0.0, "w_q": 0.0, "C/U": 0.0, "served": 0.0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = getattr(path[-1], "key", None)
        gb["all"] += leaf.nbytes / 1e9
        if any(getattr(k, "key", None) == rebranch.SERVED_KEY
               for k in path):
            gb["served"] += leaf.nbytes / 1e9
        elif name == "w_q":
            gb["w_q"] += leaf.nbytes / 1e9
        elif name in ("C", "U"):
            gb["C/U"] += leaf.nbytes / 1e9
    return gb


def run_cnn(cfg, *, seed: int, model_id: str = CNN_ID,
            batch: int = CNN_BATCH) -> dict:
    """Serve ``cfg`` with seeded branches on 'pallas_fused' via
    serve.load; compare with 'int8_native' on the same params; count the
    Pallas kernels of the compiled forward."""
    serve.register(serve.ModelEntry(model_id=model_id, config=lambda: cfg,
                                    engine="pallas_fused"), override=True)
    t0 = time.perf_counter()
    model, _ = serve.compile_entry(model_id)
    bare = model.init(jax.random.PRNGKey(seed))
    server = serve.load(model_id, n_slots=batch,
                        params=with_branches(bare, seed + 2, CONV_FAN_IN))
    jax.block_until_ready(server.params)
    t_setup = time.perf_counter() - t0
    images = _images(seed + 1, 2 * batch, cfg)
    outs, walls = [], []
    for lo in (0, batch):
        t0 = time.perf_counter()
        outs.append(server.submit(images[lo:lo + batch]))
        walls.append(time.perf_counter() - t0)
    got = np.concatenate(outs, 0)
    grid = cfg.input_size // 32
    _check(got.shape == (2 * batch, grid, grid, cfg.head_anchors,
                         5 + cfg.head_classes),
           f"cnn output shape {got.shape}")
    _check(bool(np.all(np.isfinite(got))), "cnn output not finite")

    compiled = jax.jit(server.model.forward).lower(
        server.params, images[:batch]).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()

    ref_model = deploy.compile_model(
        cfg, plan=plan_lib.solve(cfg, None, engine="int8_native"))
    ref_fwd = jax.jit(ref_model.forward)

    def ref(params):
        return np.concatenate([np.asarray(ref_fwd(params,
                                                  images[lo:lo + batch]))
                               for lo in (0, batch)], 0)

    want = ref(server.params)
    err_max, err = _rel_err(got, want)
    _check(err <= CNN_RTOL,
           f"cnn: pallas_fused vs int8_native rel L2 err {err} > {CNN_RTOL}")
    branch_effect = _branch_effect(ref(bare), want)
    serve.evict(model_id)
    return {"setup_s": t_setup, "first_batch_s": walls[0],
            "second_batch_s": walls[1], "pallas_kernels": n_kernels,
            "rel_err": err, "rel_err_max": err_max,
            "branch_effect": branch_effect, "shape": got.shape,
            "args_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9}


def _solo_logits(prefill, decode, model, params, prompt, served, max_len):
    """Solo prefill+decode of one prompt, teacher-forced on the served
    tokens: row i holds the logits served token i was picked from."""
    cache = model.init_cache(1, max_len, dtype=jnp.float32)
    lg, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])}, cache)
    rows = [lg[0, -1]]
    for tok in served[:-1]:
        lg, cache = decode(params, jnp.asarray([[tok]], jnp.int32), cache)
        rows.append(lg[0, -1])
    return np.asarray(jnp.stack(rows), np.float32)


def run_lm(cfg, *, seed: int, model_id: str = LM_ID, rows: int = LM_ROWS,
           max_len: int = LM_MAX_LEN, n_blocks: int = LM_N_BLOCKS,
           new_tokens: int = LM_NEW_TOKENS) -> dict:
    """Serve ``cfg`` through serve.load on a paged pool; check every
    request against solo prefill+decode."""
    serve.register(serve.ModelEntry(model_id=model_id, config=lambda: cfg),
                   override=True)
    rng = np.random.default_rng(seed)
    lo, hi = LM_PROMPT_LEN
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(lo, hi + 1)))
               for _ in range(LM_REQUESTS)]
    t0 = time.perf_counter()
    model, _ = serve.compile_entry(model_id)
    params = with_branches(model.init(jax.random.PRNGKey(seed)), seed + 2,
                           LM_FAN_IN)
    server = serve.load(model_id, params=params, paged=True, n_slots=rows,
                        max_len=max_len, n_blocks=n_blocks,
                        block_size=LM_BLOCK)
    jax.block_until_ready(server.params)
    t_setup = time.perf_counter() - t0

    def serve_all():
        t0 = time.perf_counter()
        reqs = [server.submit(p, new_tokens) for p in prompts]
        server.drain(max_steps=100 * LM_REQUESTS * new_tokens)
        _check(all(r.done for r in reqs), "lm: requests did not finish")
        return [list(r.tokens) for r in reqs], time.perf_counter() - t0

    served, t_cold = serve_all()
    again, t_warm = serve_all()
    _check(again == served, "lm: a second pass served other tokens")

    # the reference runs on the tree as given, not the served one
    # (``server.params``, which adds each site's derived branch)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    agree = total = 0
    worst, first_div = 0.0, None
    for i, (p, toks) in enumerate(zip(prompts, served)):
        _check(len(toks) == new_tokens, f"lm: request {i} got {len(toks)}")
        ref = _solo_logits(prefill, decode, model, params, p, toks, max_len)
        _check(bool(np.all(np.isfinite(ref))), "lm: reference not finite")
        for j, tok in enumerate(toks):
            top = int(np.argmax(ref[j]))
            total += 1
            if tok == top:
                agree += 1
                continue
            gap = float(ref[j, top] - ref[j, tok]) / float(
                np.max(np.abs(ref[j])))
            worst = max(worst, gap)
            if first_div is None:
                first_div = (i, j, tok, top, gap)
            _check(gap <= LM_TIE_RTOL,
                   f"lm: request {i} token {j}: served {tok}, solo greedy "
                   f"{top}, logit gap {gap} of max > {LM_TIE_RTOL}")
    return {"setup_s": t_setup, "cold_wall_s": t_cold, "warm_wall_s": t_warm,
            "prompt_lens": [int(p.size) for p in prompts],
            "agree": agree, "total": total, "worst_gap": worst,
            "first_divergence": first_div,
            "param_gb": _param_gb(server.params)}


def run_sharded(cfg, *, seed: int, n_chips: int) -> dict:
    """'pallas_sharded' over an n-chip H mesh vs one-chip 'pallas_fused'
    on a batch of CNN_BATCH images, with zero and with seeded branches."""
    def deployed(engine, mesh=None):
        return deploy.compile_model(
            cfg, plan=plan_lib.solve(cfg, None, engine=engine), mesh=mesh)

    sharded = deployed("pallas_sharded", make_cnn_serve_mesh(n_chips))
    bare = sharded.init(jax.random.PRNGKey(seed))
    params = {"bare": bare,
              "seeded": with_branches(bare, seed + 2, CONV_FAN_IN)}
    images = _images(seed + 1, CNN_BATCH, cfg)
    t0 = time.perf_counter()
    fused = jax.jit(deployed("pallas_fused").forward).lower(
        bare, images).compile()
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    # the engine warns, once per geometry and process, for each conv
    # whose halo does not fit the mesh and runs unsharded instead
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jax.jit(sharded.forward).lower(bare, images).compile()
    t_sharded = time.perf_counter() - t0
    fallback = [str(w.message) for w in caught
                if str(w.message).startswith("pallas_sharded:")]
    want = {k: np.asarray(fused(p, images)) for k, p in params.items()}
    got = {k: np.asarray(compiled(p, images)) for k, p in params.items()}
    for k in params:
        _check(got[k].shape == want[k].shape, f"sharded shape {got[k].shape}")
        _check(bool(np.all(np.isfinite(got[k]))), "sharded output not finite")
    err_trunk, _ = _rel_err(got["bare"], want["bare"])
    _check(err_trunk <= SHARDED_RTOL,
           f"pallas_sharded vs pallas_fused, zero branches: max-abs err "
           f"{err_trunk} > {SHARDED_RTOL}")
    _, err = _rel_err(got["seeded"], want["seeded"])
    _check(err <= CNN_RTOL,
           f"pallas_sharded vs pallas_fused, seeded branches: rel L2 err "
           f"{err} > {CNN_RTOL}")
    hlo = compiled.as_text()
    return {"compile_s": {"pallas_fused": t_fused,
                          "pallas_sharded": t_sharded},
            "rel_err_trunk": err_trunk, "rel_err": err,
            "branch_effect": _branch_effect(want["bare"], want["seeded"]),
            "pallas_kernels": hlo.count("tpu_custom_call"),
            "halo_permutes": len(_PERMUTE.findall(hlo)),
            "fallback": fallback}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); not running on it", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    tag = f"{dev.platform}:{dev.device_kind}"
    print(f"devices: {len(devices)} x {tag}; compile cache {cache_dir}")
    darknet = paper_models.DARKNET19_YOLO
    n_sites = len(cnn.conv_site_shapes(darknet))

    if args.chips == 4:
        r = run_sharded(darknet, seed=SEED, n_chips=args.chips)
        print(f"[sharded] darknet19 {darknet.input_size}px batch "
              f"{CNN_BATCH}: pallas_sharded on a 4-chip H mesh vs 1-chip "
              f"pallas_fused; zero branches max-abs err over max |ref| "
              f"{r['rel_err_trunk']:.3e} (tol {SHARDED_RTOL:g}), seeded "
              f"branches rel L2 err {r['rel_err']:.3e} (tol {CNN_RTOL:g}); "
              f"the branches move the pallas_fused output by rel L2 "
              f"{r['branch_effect']:.3e}")
        print(f"[sharded] compiled forward: {r['pallas_kernels']} Pallas "
              f"kernels (tpu_custom_call) for {n_sites} ROM conv sites, "
              f"{r['halo_permutes']} halo collective-permutes")
        _check(r["pallas_kernels"] >= n_sites and r["halo_permutes"] > 0,
               "sharded: a site skipped pallas_call or no halo moved")
        print(f"[sharded] unsharded-fallback warnings from the engine: "
              f"{r['fallback'] or 'none'}")
        print(f"[sharded] compile (single run, {tag}): " + ", ".join(
            f"{k} {v:.3f} s" for k, v in r["compile_s"].items()))
    else:
        r = run_cnn(darknet, seed=SEED)
        print(f"[cnn] {CNN_ID} output {r['shape']}: Pallas kernels "
              f"(tpu_custom_call) in the compiled forward: "
              f"{r['pallas_kernels']} for {n_sites} ROM conv sites")
        _check(r["pallas_kernels"] >= n_sites,
               f"cnn: {r['pallas_kernels']} Pallas kernels for {n_sites} "
               f"ROM conv sites (some site skipped pallas_call)")
        print(f"[cnn] pallas_fused vs int8_native: rel L2 err "
              f"{r['rel_err']:.3e} (tol {CNN_RTOL:g}), max-abs err over "
              f"max |ref| {r['rel_err_max']:.3e}; the seeded branches move "
              f"the int8_native output by rel L2 {r['branch_effect']:.3e}")
        print(f"[cnn] compiled forward memory ({tag}): arguments "
              f"{r['args_gb']:.3f} GB, temporaries {r['temp_gb']:.3f} GB")
        print(f"[cnn] times (single unbenchmarked run, {tag}): set-up "
              f"{r['setup_s']:.3f} s, first batch incl. compile "
              f"{r['first_batch_s']:.3f} s, second batch "
              f"{r['second_batch_s']:.3f} s")
        r = run_lm(configs.get("qwen2_vl_2b"), seed=SEED)
        print(f"[lm] {LM_ID}: {LM_REQUESTS} requests, prompts "
              f"{r['prompt_lens']}, {LM_NEW_TOKENS} new tokens each; "
              f"greedy agreement with solo prefill+decode "
              f"{r['agree']}/{r['total']}, worst near-tie gap "
              f"{r['worst_gap']:.3e} (tol {LM_TIE_RTOL:g})")
        print(f"[lm] params, seeded branches: " + ", ".join(
            f"{k} {v:.3f} GB" for k, v in r["param_gb"].items()))
        print(f"[lm] first divergence (request, position, served, solo, "
              f"gap): {r['first_divergence'] or 'none'}")
        print(f"[lm] times (single unbenchmarked run, {tag}): set-up "
              f"{r['setup_s']:.3f} s, requests cold incl. compile "
              f"{r['cold_wall_s']:.3f} s, warm {r['warm_wall_s']:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

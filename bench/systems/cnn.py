"""A ReBranch CNN served by ``CNNServer`` (``serve.load``), closed loop.

Set-up: the model id is registered with the configuration's engine and
the minimum-area plan (every site a ROM trunk with its SRAM branch);
the weights are drawn on the device from the seed (``weights.make``)
and handed to ``serve.load(params=...)``; a pool of seeded frames is
held on the host; one request warms the single program the window runs.

Window: one client submits ``frames_per_request`` frames, waits for the
detector output on the host, and submits again, for ``seconds``.  Each
request pays the host-to-device copy and the device-to-host copy.

Check: a sample of the window's requests, drawn from the seed
(reservoir sampling), is run through the plain reference on the same
frames and weights; the number compared is the largest relative L2
distance of a request's output from the reference's.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.counts import conv as conv_counts

# Limit on the largest rel L2 distance of a sampled request's output
# from the reference (see PERF.md for the readings it was set from).
REL_L2_LIMIT = 0.3
# a deployed branch is trained, not zero: each core is drawn He-scaled
# times this, so every branch moves its layer's output
BRANCH_SCALE = 0.3


def cnn_config(b: dict):
    """The program's ``CNNConfig`` for a configuration file's body."""
    from repro.core.rebranch import ReBranchSpec
    from repro.models import cnn
    return cnn.CNNConfig(
        name=b["model"], input_size=b["input_size"],
        head_anchors=b["head_anchors"], head_classes=b["head_classes"],
        rebranch=ReBranchSpec(d_ratio=b["d_ratio"], u_ratio=b["u_ratio"]))


def rule(names, sd, key):
    """One CNN leaf: int8 ROM codes with per-channel scales, C/U/core
    projections, seeded batch-norm statistics, the float predictor."""
    leaf = names[-1]
    shape = sd.shape
    if leaf == "w_q":                         # [k, k, c_in, c_out]
        return weights.int8_codes(key, shape)
    if leaf == "w_scale":                     # [1, 1, 1, c_out]
        return None                           # filled by the site rule
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("C", "U"):                    # [1, 1, c_in, c_out]
        return n / math.sqrt(shape[2])
    if leaf == "core":
        return n * BRANCH_SCALE * math.sqrt(2.0 / math.prod(shape[:3]))
    if leaf == "w":                           # the float 1x1 predictor
        return n * math.sqrt(2.0 / math.prod(shape[:3]))
    if leaf == "scale":
        return 1.0 + 0.1 * n
    if leaf in ("bias", "mean"):
        return 0.1 * n
    if leaf == "var":
        return jnp.exp(0.2 * n)
    raise KeyError(f"no rule for CNN leaf {names}")


def _cnn_rule(shapes):
    """``rule`` plus the trunk scales, which need the site's fan-in."""
    fan_in = {}
    for path, sd in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = weights.path_names(path)
        if names[-1] == "w_q":
            fan_in[names[:-1]] = math.prod(sd.shape[:3])

    def draw(names, sd, key):
        if names[-1] == "w_scale":
            std = math.sqrt(2.0 / fan_in[names[:-1]])
            return weights.code_scale(key, sd.shape, std)
        return rule(names, sd, key).astype(sd.dtype)
    return draw


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from ``rng``."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = item


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    requests: int
    images: int
    sample: list
    traced_images: int


class System:
    def __init__(self, run):
        self.run = run
        self.body = run.config["body"]
        self.traffic = run.traffic
        self.per = int(self.traffic.spec["frames_per_request"])

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro import serve
        from repro.models import cnn
        marks = [("start", time.perf_counter())]
        cfg = cnn_config(self.body)
        got = [s[:5] for s in cnn.conv_site_shapes(cfg)]
        if got != conv_counts.sites(self.body):
            raise ValueError("the program's conv sites differ from the "
                             "configuration file's plan")
        model_id = f"bench-{self.run.config['name']}"
        serve.register(serve.ModelEntry(model_id=model_id,
                                        config=lambda: cfg,
                                        engine=self.body["engine"]),
                       override=True)
        model, _ = serve.compile_entry(model_id)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.params = weights.make(shapes, self.run.seed, _cnn_rule(shapes))
        jax.block_until_ready(self.params)
        marks.append(("plan and weights", time.perf_counter()))
        self.server = serve.load(model_id, params=self.params,
                                 n_slots=self.per)
        self.frames = self.traffic.frames(self.body["input_size"])
        marks.append(("serve.load and frames", time.perf_counter()))
        for _ in range(2):                    # the one program, warm
            self.server.submit(self.frames[:self.per])
        marks.append(("warm-up", time.perf_counter()))
        self.setup_s = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float) -> Window:
        tracer = self.run.tracer
        pick = self.traffic.rng(4)
        pool = self.frames.shape[0]
        sample = Reservoir(int(self.traffic.spec["check_requests"]),
                           self.traffic.rng(5))
        requests = images = traced = 0
        t0 = time.perf_counter()
        now = t0
        while now - t0 < seconds:
            tracer.tick(now - t0)
            idx = pick.integers(0, pool, size=self.per)
            out = self.server.submit(self.frames[idx])
            requests += 1
            images += self.per
            traced += self.per if tracer.active else 0
            sample.offer((idx, out))
            now = time.perf_counter()
        tracer.stop()
        return Window(t0, now, requests, images, sample.items, traced)

    def end_to_end(self, w: Window) -> dict:
        return {"images_per_s": (w.images / (w.t_end - w.t0), "images/s")}

    def report(self, w: Window) -> list[str]:
        return [f"window {w.t_end - w.t0:.3f} s: {w.requests} requests of "
                f"{self.per} frames, {w.images} images; checked "
                f"{len(w.sample)} requests",
                "set-up: " + ", ".join(f"{k} {v:.3f} s"
                                       for k, v in self.setup_s.items())]

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        """Nothing but the weights outlives the window; the reference
        takes the same arrays."""
        self.server = None

    def check(self, w: Window) -> dict:
        ref = self.run.manifest.reference(self.body["reference"])
        worst = 0.0
        for idx, out in w.sample:
            want = np.asarray(ref.forward(self.params,
                                          jnp.asarray(self.frames[idx]),
                                          self.body), np.float64)
            got = np.asarray(out, np.float64)
            if got.shape != want.shape or not np.all(np.isfinite(got)):
                worst = math.inf
                break
            worst = max(worst, float(np.linalg.norm(got - want)
                                     / np.linalg.norm(want)))
        return {"rel_l2_max": {"value": worst, "limit": REL_L2_LIMIT,
                               "pass": bool(worst <= REL_L2_LIMIT)}}

    # -- per-layer readers' view --------------------------------------------
    def layer_view(self, w: Window) -> dict:
        return {"body": self.body, "batch": self.per,
                "forward": conv_counts.forward_work(self.body, self.per),
                "traced_images": w.traced_images}

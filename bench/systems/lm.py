"""A ReBranch decoder LM served by ``LMServer`` (``serve.load``).

Set-up: the model id is registered with the configuration's engine and
the minimum-area plan; the weights are drawn on the device from the
seed (``weights.make``) and handed to ``serve.load(params=...)`` with
the configuration's paged pool; warm-up requests cover every prefill
length (each chunk-tail length is its own program) and every count of
blocks a prompt's adoption scatters that the mix can draw, and nothing
else.  A closed-loop mix then fills every row before the window opens,
with the first requests' budgets cut so that they finish spread over
the window.

Window: the benchmark calls ``LMServer.step()`` itself and reads each
request's tokens after every step (a token is delivered when the step
that made it returns).  Closed loop: each client submits its next
request as soon as its last finishes.  Open loop: requests are
submitted when due on the wall clock, and time to first token counts
from the due time; arrivals stop at ``seconds`` and the window closes
when every request that arrived has its first token.

Check: a sample of the requests the window finished, drawn from the
seed, with the longest among them, is run through the plain reference
(prompt and served tokens at once).  The number compared is the widest
gap by which a served token's reference logit lies below the
reference's best at that position, over the row's largest |logit|.
"""

from __future__ import annotations

import dataclasses
import gc
import itertools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen, weights
from bench.counts import lm as lm_counts

# Limit on the widest served-token gap (see PERF.md for the readings it
# was set from).
GAP_LIMIT = 0.3
BRANCH_SCALE = 0.3
# The embedding's standard deviation.  With a table as large as the
# layers' outputs the tied readout of random weights mostly repeats the
# current token whatever the context; Qwen2 initialises it at 0.02.
EMBED_STD = 0.02


def _lm_rule(shapes):
    """Trunk codes with per-channel scales (std 1/sqrt(d_in)), branch
    projections, cores (He-scaled times BRANCH_SCALE), biases, norm
    scales, and an int8 embedding table with a scale per token (std
    EMBED_STD)."""
    fan_in = {}
    for path, sd in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = weights.path_names(path)
        if names[-1] == "w_q":
            fan_in[names[:-1]] = sd.shape[-2]

    def draw(names, sd, key):
        leaf, shape = names[-1], sd.shape
        if leaf in ("w_q", "table_q"):
            return weights.int8_codes(key, shape)
        if leaf == "w_scale":
            return weights.code_scale(key, shape,
                                      1.0 / math.sqrt(fan_in[names[:-1]]))
        if leaf == "table_scale":             # std 0.02, as Qwen2's init
            return weights.code_scale(key, shape, EMBED_STD)
        n = jax.random.normal(key, shape, jnp.float32)
        if leaf in ("C", "U"):
            out = n / math.sqrt(shape[-2])
        elif leaf == "core":
            out = n * BRANCH_SCALE * math.sqrt(2.0 / shape[-2])
        elif leaf == "b":
            out = 0.1 * n
        elif leaf == "scale":
            out = 1.0 + 0.1 * n
        else:
            raise KeyError(f"no rule for LM leaf {names}")
        return out.astype(sd.dtype)
    return draw


def arch_config(b: dict):
    """The program's ``ArchConfig`` for a configuration file's body."""
    from repro.core.rebranch import ReBranchSpec
    from repro.models.config import ArchConfig
    return ArchConfig(
        name=b["name"], family=b["family"],
        num_layers=b["num_hidden_layers"], d_model=b["hidden_size"],
        num_heads=b["num_attention_heads"],
        num_kv_heads=b["num_key_value_heads"],
        d_ff=b["intermediate_size"], vocab_size=b["vocab_size"],
        mrope=b["rope_scaling"]["type"] == "mrope",
        qkv_bias=True, rope_theta=float(b["rope_theta"]),
        norm_eps=float(b["rms_norm_eps"]),
        tie_embeddings=bool(b["tie_word_embeddings"]), dtype=b["dtype"],
        rebranch=ReBranchSpec(d_ratio=b["rebranch"]["d_ratio"],
                              u_ratio=b["rebranch"]["u_ratio"]))


def warm_lengths(lo: int, hi: int, chunk: int, block: int) -> list[int]:
    """Prompt lengths in [lo, hi] that between them make every prefill
    call length (whole prompts up to ``chunk``, then each chunk-tail
    length) and every count of ``block``-position blocks an adoption
    scatters, that prompts in [lo, hi] can make."""
    calls, grants, out = set(), set(), []
    for n in range(lo, hi + 1):
        c = {n} if n <= chunk or not chunk else \
            {chunk, n - chunk * ((n - 1) // chunk)}
        g = -(-n // block)
        if not c <= calls or g not in grants:
            calls |= c
            grants.add(g)
            out.append(n)
    return out


def _pctl(x, q):
    return float(np.percentile(np.asarray(x, np.float64), q)) if len(x) \
        else math.nan


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    requests: int                 # in flight at the open or submitted
    tokens: int                   # tokens delivered inside the window
    gaps_s: list                  # every inter-token gap in the window
    ttft_s: list                  # open loop: per request
    late_s: list                  # open loop: submit minus due
    admit_wait_s: list            # due (or submit) to first prefill chunk
    step_rows: list               # rows decoded, per step that decoded
    finished: list                # Request objects done by the close
    traced_steps: list            # live lengths per step while tracing
    traced_tokens: int


class System:
    def __init__(self, run):
        self.run = run
        self.body = run.config["body"]
        self.traffic = run.traffic
        self.serving = self.body["serving"]
        self.vocab = int(self.body["vocab_size"])
        self.content = self.traffic.rng(6)

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro import serve
        marks = [("start", time.perf_counter())]
        cfg = arch_config(self.body)
        model_id = f"bench-{self.run.config['name']}"
        serve.register(serve.ModelEntry(model_id=model_id,
                                        config=lambda: cfg,
                                        engine=self.body["engine"]),
                       override=True)
        model, _ = serve.compile_entry(model_id)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.params = weights.make(shapes, self.run.seed, _lm_rule(shapes))
        jax.block_until_ready(self.params)
        marks.append(("plan and weights", time.perf_counter()))
        s = self.serving
        self.kv_dtype = jnp.dtype(s["kv_dtype"])
        self.server = serve.load(
            model_id, params=self.params, paged=bool(s["paged"]),
            n_slots=int(s["rows"]), max_len=int(s["max_len"]),
            dtype=self.kv_dtype, block_size=s.get("block_size"),
            prefill_chunk=s.get("prefill_chunk"))
        marks.append(("serve.load", time.perf_counter()))
        self._warm()
        marks.append(("warm-up", time.perf_counter()))
        self.sizes = itertools.cycle(self.traffic.closed_requests()) \
            if self.traffic.loop == "closed" else None
        self.live = []
        if self.traffic.loop == "closed":
            self._fill()
            marks.append(("fill", time.perf_counter()))
        self.setup_s = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    def _prompt(self, n: int) -> np.ndarray:
        return self.traffic.prompt(n, self.vocab, self.content)

    def _warm(self) -> None:
        b = self.server.batcher
        lo, hi = loadgen.length_range(self.traffic.spec["prompt_len"])
        block = getattr(b.pool, "block_size", hi)
        for n in warm_lengths(lo, hi, b.prefill_chunk, block):
            self.server.submit(self._prompt(n), 2)
        self.server.drain()

    def _fill(self) -> None:
        """One request per client, the budgets of the first cut so that
        the rows finish spread over the window; every row is prefilled
        and decoding when this returns."""
        clients = int(self.traffic.spec["clients"])
        for i in range(clients):
            p, o = next(self.sizes)
            o = max(2, o - (o * i) // clients)
            self.live.append(self.server.submit(self._prompt(p), o))
        b = self.server.batcher
        while b.queued or b.prefilling:
            self.server.step()

    def _next(self):
        p, o = next(self.sizes)
        return self.server.submit(self._prompt(p), o)

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float) -> Window:
        tracer = self.run.tracer
        b = self.server.batcher
        w = Window(0.0, 0.0, 0, 0, [], [], [], [], [], [], [], 0)
        open_loop = self.traffic.loop == "open"
        sched = self.traffic.open_schedule(seconds) if open_loop else []
        due = {}                      # rid -> due time (open loop)
        seen = {r.rid: len(r.tokens) for r in self.live}
        last = {}                     # rid -> time of its latest token
        # rid -> time of its first token (before the window: None)
        first = {r.rid: None for r in self.live if r.tokens}
        order = []                    # requests in submit order
        admitted = 0
        w.requests = len(self.live)   # in flight as the window opens
        t0 = time.perf_counter()
        w.t0 = now = t0
        i = 0
        while True:
            tracer.tick(now - t0)
            if open_loop:
                while i < len(sched) and t0 + sched[i][0] <= now:
                    d, p, o = sched[i]
                    r = self.server.submit(self._prompt(p), o)
                    due[r.rid] = t0 + d
                    w.late_s.append(now - (t0 + d))
                    self.live.append(r)
                    order.append(r)
                    seen[r.rid] = 0
                    w.requests += 1
                    i += 1
                if i == len(sched) and all(r.rid in first for r in order):
                    break
                if b.idle:
                    time.sleep(max(0.0, t0 + sched[i][0]
                                   - time.perf_counter()))
                    now = time.perf_counter()
                    continue
            elif now - t0 >= seconds:
                break
            start = now
            self.server.step()
            now = time.perf_counter()
            if open_loop:     # requests whose first chunk ran this step
                n_admitted = len(order) - b.queued
                for r in order[admitted:n_admitted]:
                    w.admit_wait_s.append(start - due[r.rid])
                admitted = max(admitted, n_admitted)
            rows, still = 0, []
            for r in self.live:
                new = len(r.tokens) - seen[r.rid]
                if new:
                    seen[r.rid] += new
                    w.tokens += new
                    if r.rid in first:
                        rows += new
                    else:                 # its first token: a prefill's
                        first[r.rid] = now
                        rows += new - 1
                        if r.rid in due:
                            w.ttft_s.append(now - due[r.rid])
                    if r.rid in last:
                        w.gaps_s.append(now - last[r.rid])
                    # tokens delivered by one step arrive together
                    w.gaps_s.extend([0.0] * (new - 1))
                    last[r.rid] = now
                if r.done:
                    w.finished.append(r)
                    if not open_loop:
                        nr = self._next()
                        seen[nr.rid] = 0
                        order.append(nr)
                        still.append(nr)
                        w.requests += 1
                else:
                    still.append(r)
            self.live = still
            if rows:
                w.step_rows.append(rows)
            if tracer.active:
                w.traced_tokens += rows
                w.traced_steps.append([r.prompt.size + len(r.tokens) - 1
                                       for r in self.live if r.tokens])
        w.t_end = now
        tracer.stop()
        return w

    def end_to_end(self, w: Window) -> dict:
        span = w.t_end - w.t0
        out = {"itl_p95_ms": (1e3 * _pctl(w.gaps_s, 95), "ms")}
        if self.traffic.loop == "open":
            out["ttft_p95_ms"] = (1e3 * _pctl(w.ttft_s, 95), "ms")
        else:
            out["tokens_per_s"] = (w.tokens / span, "tokens/s")
        return out

    def report(self, w: Window) -> list[str]:
        lines = [f"window {w.t_end - w.t0:.3f} s: {w.requests} requests "
                 f"served, {len(w.finished)} finished, {w.tokens} tokens, "
                 f"{len(w.gaps_s)} inter-token gaps, {len(w.step_rows)} "
                 f"decode steps, mean rows {np.mean(w.step_rows or [0]):.2f}"]
        if w.ttft_s:
            lines.append(f"time to first token: {len(w.ttft_s)} requests, "
                         f"p50 {1e3 * _pctl(w.ttft_s, 50):.1f} ms, p95 "
                         f"{1e3 * _pctl(w.ttft_s, 95):.1f} ms")
        lines.append("set-up: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in self.setup_s.items()))
        if w.late_s:
            lines.append(f"open-loop generator lateness: p50 "
                         f"{1e3 * _pctl(w.late_s, 50):.2f} ms, max "
                         f"{1e3 * max(w.late_s):.2f} ms")
        return lines

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        """Free the KV pool and every compiled step's state; the
        reference keeps only the weights."""
        self.server = None
        self.live = []
        gc.collect()

    def sample(self, w: Window) -> list:
        done = sorted(w.finished, key=lambda r: r.rid)
        if not done:
            return []
        longest = max(done, key=lambda r: r.prompt.size + len(r.tokens))
        rest = [r for r in done if r is not longest]
        k = min(len(rest), int(self.traffic.spec["check_requests"]) - 1)
        pick = self.traffic.rng(5).choice(len(rest), size=k, replace=False)
        return [longest] + [rest[j] for j in sorted(pick)]

    def gaps(self, req, control_bits: int | None = None) -> np.ndarray:
        """Per served token: the reference's best logit minus the logit
        of the token chosen, over the row's largest |logit|.  The token
        chosen is the served one, or with ``control_bits`` the one the
        reference puts first with its trunk in that many bits."""
        ref = self.run.manifest.reference(self.body["reference"])
        ids = np.concatenate([req.prompt, np.asarray(req.tokens[:-1],
                                                     np.int32)])
        pos = np.arange(req.prompt.size - 1, ids.size)
        pad = int(self.serving["max_len"])
        want = ref.logits(self.params, ids, pos, self.body, 8, pad)
        if control_bits is None:
            chosen = np.asarray(req.tokens, np.int64)
        else:
            chosen = np.argmax(ref.logits(self.params, ids, pos, self.body,
                                          control_bits, pad), -1)
        best = want.max(-1)
        got = want[np.arange(pos.size), chosen]
        return (best - got) / np.abs(want).max(-1)

    def check(self, w: Window) -> dict:
        worst, served = 0.0, 0
        for r in self.sample(w):
            if len(r.tokens) != r.max_new_tokens:
                worst = math.inf
                break
            worst = max(worst, float(self.gaps(r).max()))
            served += len(r.tokens)
        if served == 0:
            worst = math.inf
        return {"token_gap_max": {"value": worst, "limit": GAP_LIMIT,
                                  "pass": bool(worst <= GAP_LIMIT)}}

    # -- per-layer readers' view --------------------------------------------
    def layer_view(self, w: Window) -> dict:
        return {"body": self.body, "kv_itemsize": self.kv_dtype.itemsize,
                "step_rows": w.step_rows, "traced_steps": w.traced_steps,
                "traced_tokens": w.traced_tokens,
                "admit_wait_s": w.admit_wait_s, "ttft_s": w.ttft_s,
                "lm_counts": lm_counts}

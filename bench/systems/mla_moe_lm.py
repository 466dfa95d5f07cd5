"""A DeepSeek-V2 decoder (latent attention, held-expert MoE) served by
``LMServer`` (``serve.load``), one chip's share of an expert-parallel
deployment.

Everything as ``bench.systems.lm`` (set-up, window, check), except:

* the program's ``ArchConfig`` comes from DeepSeek-V2's ``config.json``
  keys (:func:`arch_config`): the router at the published width
  (``published.n_routed_experts``), the experts held here
  (``n_routed_experts``, the first ones), YaRN, the leading dense layer;
* the weight rule also draws the router (std 1/sqrt(d)) and the expert
  stacks (the rule of ``lm`` applied to stacked leaves);
* prompt ids follow the mix's ``prompt_ids``: Zipf(s) ranks mapped onto
  the vocabulary slice through a permutation drawn from the seed (text
  is Zipfian; uniform ids would spread the router's load evenly);
* the held experts' assignment counter, which the decode step keeps on
  the device in the cache, is read as the window opens and as it closes
  (never inside it);
* the check's limit is this configuration's (``GAP_LIMIT``).
"""

from __future__ import annotations

import itertools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.counts import mla_moe as mla_moe_counts
from bench.systems import lm

# Limit on the widest served-token gap, set from eleven seeds on a TPU
# v5e (PERF.md section 2): the program read 0.115-0.185, the int4
# control 1.207-1.507 (its least single request 0.831).
GAP_LIMIT = 0.3


def arch_config(b: dict):
    """The program's ``ArchConfig`` for a DeepSeek-V2 configuration
    file's body; raises for published settings the program does not
    implement."""
    from repro.core.rebranch import ReBranchSpec
    from repro.models.config import ArchConfig, YaRN
    unsupported = {
        "q_lora_rank": (b["q_lora_rank"], None),
        "scoring_func": (b["scoring_func"], "softmax"),
        "topk_method": (b["topk_method"], "greedy"),
        "routed_scaling_factor": (float(b["routed_scaling_factor"]), 1.0),
        "moe_layer_freq": (b["moe_layer_freq"], 1),
        "hidden_act": (b["hidden_act"], "silu"),
        "attention_bias": (b["attention_bias"], False),
        "tie_word_embeddings": (b["tie_word_embeddings"], False),
    }
    for key, (got, want) in unsupported.items():
        if got != want:
            raise ValueError(f"{key}={got!r}: the program implements "
                             f"{want!r} only")
    y = b["rope_scaling"]
    return ArchConfig(
        name=b["name"], family=b["family"],
        num_layers=b["num_hidden_layers"], d_model=b["hidden_size"],
        num_heads=b["num_attention_heads"],
        num_kv_heads=b["num_key_value_heads"],
        d_ff=b["intermediate_size"], moe_d_ff=b["moe_intermediate_size"],
        vocab_size=b["vocab_size"],
        num_experts=b["published"]["n_routed_experts"],
        experts_held=(0, b["n_routed_experts"]),
        num_experts_per_tok=b["num_experts_per_tok"],
        num_shared_experts=b["n_shared_experts"],
        moe_norm_topk=bool(b["norm_topk_prob"]), shared_expert_gate=False,
        first_k_dense=b["first_k_dense_replace"],
        kv_lora_rank=b["kv_lora_rank"],
        qk_nope_head_dim=b["qk_nope_head_dim"],
        qk_rope_head_dim=b["qk_rope_head_dim"],
        v_head_dim=b["v_head_dim"], rope_theta=float(b["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(y["factor"]),
            original_max_position_embeddings=int(
                y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        norm_eps=float(b["rms_norm_eps"]), tie_embeddings=False,
        dtype=b["dtype"],
        rebranch=ReBranchSpec(d_ratio=b["rebranch"]["d_ratio"],
                              u_ratio=b["rebranch"]["u_ratio"]))


def _rule(shapes):
    """``lm``'s rule, and the router's weights N(0, 1/d)."""
    base = lm._lm_rule(shapes)

    def draw(names, sd, key):
        if names[-3:] == ("router", "sram", "w"):
            n = jax.random.normal(key, sd.shape, jnp.float32)
            return (n / math.sqrt(sd.shape[-2])).astype(sd.dtype)
        return base(names, sd, key)
    return draw


class System(lm.System):
    def __init__(self, run):
        super().__init__(run)
        ids = self.traffic.spec["prompt_ids"]
        if ids["dist"] != "zipf":
            raise ValueError(f"unknown prompt id distribution "
                             f"{ids['dist']!r}")
        ranks = np.arange(1, self.vocab + 1, dtype=np.float64)
        p = ranks ** -float(ids["s"])
        self._id_cdf = np.cumsum(p / p.sum())
        self._id_of_rank = self.traffic.rng(7).permutation(self.vocab)
        self.held = None

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
        self.params = weights.make(shapes, self.run.seed, _rule(shapes))
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
        ranks = np.searchsorted(self._id_cdf, self.content.random(n))
        ranks = np.minimum(ranks, self.vocab - 1)
        return self._id_of_rank[ranks].astype(np.int32)

    # -- window ---------------------------------------------------------------
    def _held_counts(self):
        """The pool's held-expert assignment counts [MoE layers, held
        experts] (a device read), or None where the program keeps none."""
        counters = self.server.pool.cache.get("counters")
        return None if counters is None else \
            np.asarray(counters["held_tokens"], np.int64)

    def window(self, seconds: float):
        before = self._held_counts()
        steps = self.server.batcher.step_count
        w = super().window(seconds)
        after = self._held_counts()
        if before is not None:
            self.held = {"counts": after - before,
                         "steps": self.server.batcher.step_count - steps}
        return w

    def report(self, w) -> list[str]:
        lines = super().report(w)
        if self.held is not None and self.held["steps"]:
            c = self.held["counts"]
            lines.append(
                f"held experts: {int(c.sum())} token assignments over "
                f"{self.held['steps']} decode steps, "
                f"{c.sum() / (self.held['steps'] * c.size):.3f} per held "
                f"expert per MoE layer per step; largest expert's share "
                f"{c.sum(0).max() / max(1, c.sum()):.4f}")
        return lines

    # -- check ----------------------------------------------------------------
    def check(self, w) -> dict:
        out = super().check(w)["token_gap_max"]
        worst = out["value"]
        return {"token_gap_max": {"value": worst, "limit": GAP_LIMIT,
                                  "pass": bool(worst <= GAP_LIMIT)}}

    # -- per-layer readers' view --------------------------------------------
    def layer_view(self, w) -> dict:
        view = super().layer_view(w)
        view.update(lm_counts=mla_moe_counts, held=self.held)
        return view

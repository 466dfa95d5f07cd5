"""The serving path's host spans (``repro.serve.trace``) and the named
scopes of the model's layers.

  * off, the recorder records nothing and ``span()`` is one shared no-op;
  * on, a paged LM run gives one ``batcher.decode`` per decode tick whose
    ``rows`` sum to the tokens decoded, one ``request.queue`` and one
    ``request.prefill`` per request, spans nested as the batcher calls
    them, and KV attributes equal to the pool's own counts;
  * one ``batcher.prepare_branch`` per served params tree built (at
    construction, after each swap), and none with the recorder off;
  * the scopes change op metadata only: the compiled decode step and CNN
    forward are the same programs without them.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.core import rebranch
from repro.models import cnn
from repro.serve import trace
from repro.serve.pool import PagedPool, SlotPool
from repro.serve.scheduler import ContinuousBatcher

LM_ID = "qwen2-vl-2b-smoke"
MAX_LEN = 48
GENS = (4, 7, 3, 6)


@pytest.fixture(scope="module")
def cell():
    model, _ = serve.compile_entry(LM_ID)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def recorder_off():
    trace.disable()
    yield
    trace.disable()


def _prompts(vocab):
    rng = np.random.default_rng(0)
    # one prompt longer than the chunk, so one request prefills in chunks
    return [rng.integers(0, vocab, size=n) for n in (6, 19, 9, 7)]


def _serve(cell, spec_k=0):
    model, params = cell
    pool = PagedPool(model, 3, 18, 8, MAX_LEN)
    b = ContinuousBatcher(model, params, pool, prefill_chunk=8,
                          spec_k=spec_k)
    reqs = [b.submit(p, g) for p, g in zip(_prompts(model.cfg.vocab_size),
                                            GENS)]
    return pool, b, reqs


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_off_records_nothing(cell):
    trace.enable()
    trace.disable()
    assert trace.span("batcher.step") is trace.span("cnn.request", rid=3)
    trace.record("request.queue", 0, 1, rid=0)
    trace.annotate(rows=1)
    _, b, _ = _serve(cell)
    b.drain(max_steps=200)
    snap = trace.snapshot()
    assert snap["spans"] == [] and snap["dropped"] == 0


def test_decode_spans_count_the_rows_decoded(cell):
    _, b, reqs = _serve(cell)
    trace.enable()
    b.drain(max_steps=200)
    trace.disable()
    spans = trace.snapshot()["spans"]
    decodes = _named(spans, "batcher.decode")
    assert len(decodes) == b.step_count
    # every token after a request's first came from a decode step
    assert sum(s["attrs"]["rows"] for s in decodes) == \
        sum(len(r.tokens) - 1 for r in reqs)
    assert all(0 < s["start"] <= s["end"] for s in spans)


def test_request_spans_and_nesting(cell):
    _, b, reqs = _serve(cell)
    trace.enable()
    b.drain(max_steps=200)
    trace.disable()
    spans = trace.snapshot()["spans"]
    parent = lambda s: spans[s["parent"]]["name"] if s["parent"] >= 0 \
        else None
    for r in reqs:
        mine = [s for s in spans if s["rid"] == r.rid]
        (queue,) = _named(mine, "request.queue")
        (prefill,) = _named(mine, "request.prefill")
        chunks = _named(mine, "batcher.prefill")
        assert len(chunks) == -(-r.prompt.size // b.prefill_chunk)
        assert [(c["attrs"]["start"], c["attrs"]["end"]) for c in chunks] \
            == [(lo, min(lo + 8, r.prompt.size))
                for lo in range(0, r.prompt.size, 8)]
        assert queue["end"] == prefill["start"] <= chunks[0]["start"]
        assert prefill["end"] >= _named(mine, "batcher.first_token")[0]["end"]
        assert queue["start"] == pytest.approx(r.submit_s * 1e9, abs=1e3)
        assert queue["parent"] == prefill["parent"] == -1
        (adopt,) = _named(mine, "pool.adopt")
        assert adopt["attrs"] == {"blocks": -(-r.prompt.size // 8)}
    expect = {"batcher.step": {None}, "batcher.admit": {"batcher.step"},
              "batcher.prefill": {"batcher.admit"},
              "pool.adopt": {"batcher.admit"},
              "batcher.first_token": {"batcher.admit"},
              "pool.prepare_step": {"batcher.step"},
              "batcher.decode": {"batcher.step"},
              "batcher.sample": {"batcher.step"},
              "batcher.retire": {"batcher.step"}}
    seen = {}
    for s in spans:
        if not s["name"].startswith("request."):
            seen.setdefault(s["name"], set()).add(parent(s))
    assert seen == expect


def test_kv_attributes_equal_the_pool_counts(cell):
    pool, b, _ = _serve(cell)
    seen = []
    decode = b._decode

    def counted(params, tok, cache):
        seen.append({"kv_live": pool.live_tokens,
                     "kv_blocks": pool.blocks_in_use,
                     "kv_reserved": pool.blocks_reserved,
                     "kv_positions": pool.n_blocks * pool.block_size})
        return decode(params, tok, cache)

    b._decode = counted
    trace.enable()
    b.drain(max_steps=200)
    trace.disable()
    got = [{k: v for k, v in s["attrs"].items() if k.startswith("kv_")}
           for s in _named(trace.snapshot()["spans"], "batcher.decode")]
    assert got == seen and len(seen) == b.step_count
    assert any(c["kv_reserved"] for c in seen)


def test_speculative_round_spans(cell):
    _, b, reqs = _serve(cell, spec_k=2)
    trace.enable()
    b.drain(max_steps=200)
    trace.disable()
    spans = trace.snapshot()["spans"]
    names = {s["name"] for s in spans}
    assert {"batcher.draft", "batcher.verify", "pool.rollback"} <= names
    assert "batcher.decode" not in names
    assert len(_named(spans, "batcher.verify")) == b.spec_rounds


@pytest.mark.parametrize("on", [True, False])
def test_prepare_branch_span_per_served_tree(cell, on):
    """One ``batcher.prepare_branch`` at construction and one after each
    swap, naming the sites derived and the bytes they hold; with the
    recorder off the batcher prepares alike and nothing is recorded."""
    model, params = cell
    _, want_sites, want_bytes = rebranch.serving_branch(
        params, jnp.dtype(model.cfg.dtype))
    branch = jax.tree.map(lambda a: a + 0.01,
                          rebranch.partition(params)[0])
    trace.enable()                        # clears what was recorded
    if not on:
        trace.disable()
    b = ContinuousBatcher(model, jax.tree.map(jnp.array, params),
                          SlotPool(model, 2, MAX_LEN), scenario="a")
    b.swap("b", branch)
    b.submit(np.arange(5), 2, scenario="b")
    b.drain(max_steps=20)
    trace.disable()
    spans = trace.snapshot()["spans"]
    assert b.branch_prepares == 2 and b.swap_count == 1
    if not on:
        assert spans == []
        return
    prep = _named(spans, "batcher.prepare_branch")
    assert [s["attrs"] for s in prep] == \
        [{"sites": want_sites, "bytes": want_bytes}] * 2
    assert want_sites == 7 * model.cfg.num_layers and want_bytes > 0
    assert prep[0]["parent"] == -1
    assert spans[prep[1]["parent"]]["name"] == "batcher.admit"


def test_cap_counts_dropped_spans(monkeypatch):
    monkeypatch.setattr(trace, "CAP", 3)
    trace.enable()
    for _ in range(5):
        with trace.span("cnn.request"):
            pass
    trace.record("request.queue", 1, 2)
    trace.disable()
    snap = trace.snapshot()
    assert len(snap["spans"]) == 3 and snap["dropped"] == 3


def test_cnn_request_spans():
    srv = serve.load("vgg8-32", n_slots=4, key=jax.random.PRNGKey(1))
    imgs = np.random.default_rng(3).normal(
        size=(6, 32, 32, 3)).astype(np.float32)
    trace.enable()
    srv.submit(imgs)
    trace.disable()
    spans = trace.snapshot()["spans"]
    (req,) = _named(spans, "cnn.request")
    assert req["attrs"] == {"frames": 6}
    kids = [s["name"] for s in spans if s["parent"] == 0]
    assert kids == ["cnn.copy_in"] + ["cnn.copy_in", "cnn.forward",
                                      "cnn.copy_out"] * 2


# -- named scopes ---------------------------------------------------------

def _no_scopes(name):
    return contextlib.nullcontext()


def _strip(hlo: str) -> str:
    """The program without its metadata (each op's, and the source
    location tables), its values renamed in order of first use: the
    numbers that make names unique follow the op names in the metadata."""
    hlo = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(?:.+\n)*", "", hlo, flags=re.M)
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(), f"%v{len(names)}"),
                  hlo)


def _decode_program(cell):
    model, params = cell
    pool = PagedPool(model, 2, 12, 8, MAX_LEN)
    tok = jnp.zeros((2, 1), jnp.int32)
    return jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, tok, pool.cache).compile().as_text()


def _cnn_program(engine):
    model_id = f"tiny-yolo-64-{engine}"
    serve.register(serve.ModelEntry(
        model_id=model_id,
        config=lambda: cnn.CNNConfig(name="tiny_yolo", input_size=64),
        engine=engine), override=True)
    model, _ = serve.compile_entry(model_id)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 64, 64, 3), jnp.float32)
    return jax.jit(model.forward).lower(params, x).compile().as_text()


@pytest.mark.parametrize("program, scopes", [
    ("decode_step", {"embed", "attention", "kv_write", "mlp", "trunk",
                     "branch", "lm_head"}),
    ("forward.pallas_fused", {"patches"}),
    ("forward.int8_native", {"trunk", "branch"}),
])
def test_scopes_change_only_metadata(cell, monkeypatch, program, scopes):
    build = (lambda: _decode_program(cell)) if program == "decode_step" \
        else (lambda: _cnn_program(program.split(".")[1]))
    scoped = build()
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    found = {part for n in names for part in n.split("/")}
    assert scopes <= found, scopes - found
    monkeypatch.setattr(jax, "named_scope", _no_scopes)
    jax.clear_caches()        # inner jitted functions trace again
    plain = build()
    assert not scopes & {p for n in re.findall(r'op_name="([^"]*)"', plain)
                         for p in n.split("/")}
    assert _strip(scoped) == _strip(plain)

"""Logical-axis sharding rules (MaxText-style) -> NamedSharding.

Models annotate activations with *logical* axes via ``shard(x, ...)``;
parameters get PartitionSpecs from their pytree path via ``param_specs``.
The mapping logical-axis -> mesh-axes is a context-scoped rule set so the
same model code runs unsharded on one CPU device and fully sharded on the
production (pod, data, model) mesh.

Divisibility: jax/GSPMD pads uneven shardings, so head counts that don't
divide the model axis (yi 56H, qwen1.5 40H) still lower — the padding
waste is surfaced by the roofline analysis instead of crashing.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> tuple of mesh axis names (tried in order, first that
# exists in the current mesh wins; missing axes mean "replicated")
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),       # data parallel over pod+data axes
    "seq": (),                      # sequence inside blocks: unsharded
    # Megatron-style sequence parallelism for the residual stream: block
    # boundaries are per-token, so the residual is sharded over the model
    # axis; GSPMD inserts all-gather at block entry (where attention needs
    # full sequence) and reduce-scatter at exit — same wire volume as the
    # all-reduces it replaces, 1/model_size the activation memory.
    "seq_sp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "embed": (),                    # residual stream replicated
    "expert": ("model",),           # EP when divisible (policy in moe.py)
    "expert_mlp": ("model",),       # per-expert hidden when EP not divisible
    "kv_seq": ("data", "model"),    # long-context cache: shard sequence
    "ssm_inner": ("model",),
    "cnn_chan": ("model",),
    # CNN serving (halo-exchange sharded conv, engine 'pallas_sharded'):
    # NHWC activations shard spatial H over the data axis; the kernel-halo
    # rows exchanged between neighbour shards inherit this same spec (a
    # halo buffer is a [N, halo_rows, W, C] slice of the activation).  W
    # is never sharded — a 2-D halo would double the exchange count for
    # no memory win at detection aspect ratios.
    "cnn_batch": ("pod",),          # image batch rides the pod axis
    "cnn_h": ("data",),             # spatial H: halo-exchange sharding
}

_state = threading.local()


def current_mesh() -> Mesh | None:
    """The mesh bound by the innermost :func:`use_mesh`, or None.

    Read at trace time (inside ``jit``), so it is this module's own
    thread-local binding and not JAX's ambient mesh context, which
    ``jax.sharding.get_mesh`` refuses to read under a trace."""
    return getattr(_state, "mesh", None)


def current_rules() -> dict[str, tuple[str, ...]]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    old_mesh = getattr(_state, "mesh", None)
    old_rules = getattr(_state, "rules", DEFAULT_RULES)
    _state.mesh = mesh
    _state.rules = {**DEFAULT_RULES, **(rules or {})}
    try:
        yield
    finally:
        _state.mesh = old_mesh
        _state.rules = old_rules


def mesh_axis_for(logical: str, mesh: Mesh | None = None) -> str | None:
    """The first mesh axis (rule order) a logical axis maps onto, or None.

    Unlike :func:`logical_to_spec` this returns the bare axis *name* —
    what shard_map callers (the halo-exchange conv engine) need to build
    in/out specs and ppermute over the right axis.  Axes of size 1 are
    skipped: sharding over them is a no-op and the caller should take
    its unsharded path.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    for a in current_rules().get(logical, ()):
        if a in mesh.axis_names and mesh.shape[a] > 1:
            return a
    return None


def logical_to_spec(axes: tuple[str | None, ...],
                    mesh: Mesh | None = None) -> P:
    """Translate logical axis names to a PartitionSpec for ``mesh``."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return P()
    names = set(mesh.axis_names)
    used: set[str] = set()
    parts = []
    for ax in axes:
        if ax is None or ax == "":
            parts.append(None)
            continue
        mesh_axes = tuple(a for a in current_rules().get(ax, ())
                          if a in names and a not in used)
        used.update(mesh_axes)
        if len(mesh_axes) == 0:
            parts.append(None)
        elif len(mesh_axes) == 1:
            parts.append(mesh_axes[0])
        else:
            parts.append(mesh_axes)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain activation sharding by logical axes (no-op without mesh).

    Size-aware: a dimension is only sharded if it divides evenly by the
    mesh axes assigned to it — otherwise that axis is dropped (replicated)
    instead of forcing GSPMD into padded/conflicting shardings (e.g. gemma
    kv=1 or yi 56H on a 16-way model axis)."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = logical_to_spec(axes, mesh)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    fixed = []
    for dim, part in zip(x.shape, parts):
        if part is None:
            fixed.append(None)
            continue
        names = part if isinstance(part, tuple) else (part,)
        size = int(np.prod([mesh.shape[n] for n in names]))
        fixed.append(part if dim % size == 0 and dim >= size else None)
    while fixed and fixed[-1] is None:
        fixed.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))


# ---------------------------------------------------------------------------
# parameter sharding from pytree paths
# ---------------------------------------------------------------------------

_WIDE_OUT = ("['q']", "['k']", "['v']", "['gate']", "['up']", "['in_proj']",
             "['x_proj']", "['dt_proj']", "['head']", "['lm_head']",
             "['shared_gate']", "['codebook_head']")
_WIDE_IN = ("['o']", "['down']", "['out_proj']")


import re as _re

_LAYER_LIST_RE = _re.compile(r"\['layers'\]\[\d+\]")


def _spec_for_param(path: str, leaf, mesh: Mesh) -> P:
    """Heuristic path->spec rules for every model family in the zoo.

    Conventions (see models/*): weights are [d_in, d_out] with the tensor-
    parallel ("wide") dim on the output side for q/k/v/gate/up/... and on
    the input side for o/down/out_proj; stacked expert weights are
    [E, d_in, d_out]; embedding tables are [V, d].  Branch compress C is
    [d_in, d_in/D] (small, replicated); core and decompress U follow the
    trunk's wide side so the branch epilogue needs no extra collective.

    Scan-over-layers archs stack per-layer params with a leading L dim
    (path has ['layers'] without an index): the rule is computed on the
    per-layer shape and L is left unsharded.
    """
    r = lambda *axes: logical_to_spec(axes, mesh)
    nd = getattr(leaf, "ndim", 0)
    stacked = ("['layers']" in path and not _LAYER_LIST_RE.search(path))
    if stacked:
        nd -= 1                            # effective per-layer ndim

    def out(spec: P) -> P:
        return P(None, *spec) if stacked else spec

    if "table_q" in path or "table_scale" in path:
        return r("vocab", None)            # embeddings are never stacked

    wide_out = any(k in path for k in _WIDE_OUT)
    wide_in = any(k in path for k in _WIDE_IN)
    is_weight = ("w_q" in path or "['w']" in path)

    if "experts" in path:
        # EP over the model axis when E divides it; otherwise TP *within*
        # each expert on its hidden dim (granite E=40, qwen2-moe E=60 on a
        # 16-way model axis take this path).
        shp = leaf.shape[1:] if stacked else leaf.shape
        m_size = mesh.shape.get("model", 1)
        ep_ok = len(shp) >= 1 and shp[0] % m_size == 0
        if nd == 3 and "w_scale" in path:            # [E, 1, d_out]
            if ep_ok:
                return out(r("expert", None, None))
            return out(P(None, None, "model")) if wide_out else out(P())
        if nd == 3:                                  # [E, d_in, d_out]
            if ep_ok:
                return out(r("expert", None, None))
            if "core" in path:
                return out(P()) if wide_out else out(P(None, "model", None))
            if wide_out:
                return out(P(None, None, "model"))
            return out(P(None, "model", None))      # down: contract dim
        if nd == 2 and "['C']" in path:              # shared compress
            return out(P()) if wide_out else out(P("model", None))
        if nd == 2 and "['U']" in path:              # shared decompress
            return out(P(None, "model")) if wide_out else out(P())
        return P()

    if nd == 2 and is_weight:
        if wide_out:
            return out(r(None, "mlp"))     # model axis on outputs
        if wide_in:
            return out(r("mlp", None))     # model axis on inputs
        return P()
    if nd == 2 and "w_scale" in path:
        if wide_out:
            return out(r(None, "mlp"))     # scales track the trunk outputs
        return P()
    # Branch tensors.  Column-parallel trunks (wide_out): C/core replicated
    # (t1 is only d_in/D wide), U sharded on outputs so t1 @ (core@U) lands
    # exactly on the trunk sharding — zero extra collectives.  Row-parallel
    # trunks (wide_in): C and core sharded on the *contracting* side so t1
    # reduce-scatters to [., d_in/D / m] and the epilogue's partial sums
    # merge into the trunk's own all-reduce.
    if nd == 2 and "['U']" in path:
        return out(r(None, "mlp")) if wide_out else P()
    if nd == 2 and "core" in path:
        return P() if wide_out else out(r("mlp", None))
    if nd == 2 and "['C']" in path:
        return P() if wide_out else out(r("mlp", None))
    return P()                             # small: replicate


def _size_check(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes whose dimension doesn't divide the mesh axes."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    fixed = []
    for dim, part in zip(shape, parts):
        if part is None:
            fixed.append(None)
            continue
        names = part if isinstance(part, tuple) else (part,)
        size = int(np.prod([mesh.shape[n] for n in names]))
        fixed.append(part if dim % size == 0 and dim >= size else None)
    while fixed and fixed[-1] is None:
        fixed.pop()
    return P(*fixed)


def param_specs(params, mesh: Mesh | None = None):
    """Pytree of PartitionSpec matching ``params``."""
    mesh = mesh or current_mesh()

    def one(path, leaf):
        p = jax.tree_util.keystr(path)
        if mesh is None:
            return P()
        spec = _spec_for_param(p, leaf, mesh)
        return _size_check(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params, mesh: Mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(params, mesh),
        is_leaf=lambda s: isinstance(s, P))

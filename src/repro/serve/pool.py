"""KV-cache pools: one resident cache, capacity owned by requests.

Two layouts behind one scheduler-facing interface (``try_admit`` /
``adopt`` / ``prepare_step`` / ``release``):

:class:`SlotPool` — dense.  One ``init_cache(n_slots, max_len)`` tree;
each request owns one full-horizon batch row for its lifetime, so a
16-token prompt pays the same bytes as a full-horizon one.

:class:`PagedPool` — paged.  The same byte budget carved into
fixed-size physical blocks shared by every row: each request holds a
block TABLE (logical block -> physical block), blocks are reserved at
admission but granted on demand as decode advances, and short requests
only ever pin the blocks they actually fill.  The attention math is
unchanged — ``models.layers`` gathers the logical view through the
table, bit-identical to the dense row at every valid position — so the
serving invariant (batched tokens == solo tokens, bitwise) holds across
both layouts.

Admission copies a solo-prefilled (batch=1, dense) cache into the
request's row/blocks — bitwise, no rescale — so a request's decode
continues from exactly the state the solo path would hold.  Retirement
just returns the capacity: stale rows/blocks are dead weight until the
next adoption overwrites them (decode may keep writing garbage for free
rows; nothing reads it because every row's validity mask follows its
own ``length``, and a paged free row's writes land in the reserved
trash block).

Pool sizing comes from the :class:`~repro.plan.PlacementPlan`'s SRAM
residency stats: the branch cores and any SRAM-resident sites already
occupy on-die SRAM, and the KV capacity lives in what remains of the
activation budget (:func:`suggest_slots` / :func:`suggest_paged`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.serve import trace


def _batch_axis(cfg) -> int:
    """Batch axis of every cache leaf: 1 under scan-stacked layers
    (leaves carry a leading L dim), 0 otherwise."""
    return 1 if getattr(cfg, "scan_layers", False) else 0


@partial(jax.jit, static_argnames=("scan",), donate_argnums=(0,))
def _scatter_blocks(pool_leaf, solo_leaf, phys, scan: bool):
    """Write the first ``len(phys)`` blocks of a dense batch=1 leaf into
    the physical blocks ``phys`` of a paged pool leaf.  The pool leaf is
    donated, so adoption updates it in place: an undonated scatter would
    hold a second copy of the whole pool leaf while it runs."""
    n = phys.shape[0]
    if scan:         # [L,P,bs,...] <- [L,1,max_len,...]
        bs = pool_leaf.shape[2]
        blocks = solo_leaf[:, 0, :n * bs].reshape(
            solo_leaf.shape[0], n, bs, *solo_leaf.shape[3:])
        return pool_leaf.at[:, phys].set(blocks.astype(pool_leaf.dtype))
    bs = pool_leaf.shape[1]   # [P,bs,...] <- [1,max_len,...]
    blocks = solo_leaf[0, :n * bs].reshape(n, bs, *solo_leaf.shape[2:])
    return pool_leaf.at[phys].set(blocks.astype(pool_leaf.dtype))


def _set_lengths(cache, new_lens: dict[int, int], scan: bool):
    """Scatter per-row ``length`` values into every layer of a serve
    cache (shared by both pools' speculative ``rollback``)."""
    rows = jnp.asarray(sorted(new_lens), jnp.int32)
    vals = jnp.asarray([new_lens[r] for r in sorted(new_lens)], jnp.int32)
    layers = cache["layers"]
    if scan:       # stacked leaves: [L, B] lengths, broadcast over L
        layers = {**layers, "length": layers["length"].at[:, rows]
                  .set(vals[None])}
    else:
        layers = [{**ld, "length": ld["length"].at[rows].set(vals)}
                  for ld in layers]
    return {**cache, "layers": layers}


class SlotPool:
    """N cache rows + a free list; adoption and release are O(1)."""

    def __init__(self, model, n_slots: int, max_len: int,
                 dtype=jnp.float32):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self.model = model
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.dtype = dtype
        self._axis = _batch_axis(model.cfg)
        self.cache = model.init_cache(n_slots, max_len, dtype=dtype)
        self._free = list(range(n_slots))[::-1]     # pop() -> slot 0 first

    # -- bookkeeping ----------------------------------------------------
    @property
    def free_slots(self) -> int:
        """Rows not currently owned by a request."""
        return len(self._free)

    @property
    def occupancy(self) -> int:
        """Rows currently owned by requests (never exceeds n_slots)."""
        return self.n_slots - len(self._free)

    def alloc(self) -> int | None:
        """Pop a free slot, or ``None`` when every row is held."""
        return self._free.pop() if self._free else None

    def try_admit(self, total_len: int) -> int | None:
        """Claim capacity for a request needing ``total_len`` positions.

        Dense rows always span the full horizon, so the only resource is
        the row itself: returns a slot or ``None`` (no starvation state
        to track).  Raises if ``total_len`` exceeds the pool horizon —
        the request could never fit, waiting won't help.
        """
        if total_len > self.max_len:
            raise ValueError(
                f"request needs {total_len} cache positions but the pool "
                f"was sized for max_len={self.max_len}")
        return self.alloc()

    def release(self, slot: int) -> None:
        """Return a slot to the free list.  Raises on out-of-range and
        double-release (both indicate scheduler bookkeeping bugs)."""
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} outside pool of {self.n_slots}")
        if slot in self._free:
            raise ValueError(f"slot {slot} double-released")
        self._free.append(slot)

    def prepare_step(self) -> None:
        """Pre-decode hook: dense rows never need new capacity (no-op;
        the paged pool grants blocks here)."""

    def prepare_tokens(self, n: int) -> None:
        """Pre-verify hook for an ``n``-token speculative block: dense
        rows span the full horizon, nothing to grant (no-op; the paged
        pool grants the covering blocks here)."""

    def counts(self) -> dict:
        """Host-side KV counts for the serving spans: none, since dense
        rows keep their lengths on the device only."""
        return {}

    def rollback(self, new_lens: dict[int, int]) -> None:
        """Truncate rows to ``{slot: new_length}`` after a speculative
        verify rejected part of a draft block.  Dense rows only need
        their device lengths reset — the rejected KV entries beyond the
        new length become stale garbage that the validity mask hides
        until the next write overwrites them (exactly like a retired
        row's leftovers)."""
        if not new_lens:
            return
        self.cache = _set_lengths(self.cache, new_lens, self._axis == 1)

    # -- cache row transfer ---------------------------------------------
    def adopt(self, slot: int, solo_cache) -> None:
        """Copy a batch=1 cache into ``slot``'s row, leaf by leaf (the
        layers' leaves; the pool's own counters stay)."""
        axis = self._axis

        def put(pool_leaf, solo_leaf):
            row = jax.lax.index_in_dim(solo_leaf, 0, axis, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                pool_leaf, row.astype(pool_leaf.dtype), slot, axis)

        self.cache = {**self.cache, "layers": jax.tree.map(
            put, self.cache["layers"], solo_cache["layers"])}

    def solo_cache(self):
        """A fresh batch=1 cache with this pool's geometry (for the
        admission prefill; same max_len so adopted rows line up)."""
        return self.model.init_cache(1, self.max_len, dtype=self.dtype)


class PagedPool:
    """Paged KV pool: shared physical blocks, per-request block tables.

    The cache tree holds ``n_blocks + 1`` physical blocks of
    ``block_size`` positions per layer (the extra one is the TRASH
    block, see below) plus a ``[n_rows, max_len/block_size]`` block
    table.  A request's life:

      ``try_admit(total)`` reserves ``ceil(total/block_size)`` blocks
      (and a table row) without touching the device — admission is
      refused unless the whole request is guaranteed to fit, so decode
      can never deadlock on a block that will never free.
      ``adopt(row, solo_cache)`` grants the blocks covering the
      prefilled prompt and scatters the dense solo row into them,
      bitwise.  ``prepare_step()`` (called by the scheduler before
      every batched decode) grants each active row the block holding
      its next write position — on-demand growth, so a request that
      retires early (EOS) never materialises its reservation's tail.
      ``release(row)`` frees the blocks and points the row's table back
      at the trash block.

    The trash block: decode writes one KV entry for EVERY batch row,
    including free rows (their output is masked, never read).  Free
    rows' table entries all point at the last physical block, so those
    garbage writes can never land inside a live request's blocks.

    Error behavior matches the geometry-error style of ``deploy.py``:
    impossible requests (``total > max_len``) raise at admission;
    double-release and foreign rows raise; a grant with no free block
    raises RuntimeError naming the reservation invariant that would
    have to be broken for it to happen.
    """

    def __init__(self, model, n_rows: int, n_blocks: int,
                 block_size: int, max_len: int, dtype=jnp.float32):
        if n_rows < 1:
            raise ValueError(f"need at least one row, got {n_rows}")
        if max_len % block_size:
            raise ValueError(
                f"block_size {block_size} does not divide max_len "
                f"{max_len} (the logical view must match the dense "
                f"cache geometry exactly)")
        if n_blocks < max_len // block_size:
            raise ValueError(
                f"{n_blocks} blocks of {block_size} cannot hold even "
                f"one full-horizon request (max_len {max_len} needs "
                f"{max_len // block_size}); shrink max_len or grow the "
                f"pool")
        self.model = model
        self.n_rows = int(n_rows)
        self.n_blocks = int(n_blocks)          # usable (trash excluded)
        self.block_size = int(block_size)
        self.max_len = int(max_len)
        self.dtype = dtype
        self._axis = _batch_axis(model.cfg)
        self._scan = self._axis == 1
        self.nb_logical = max_len // block_size
        # +1: the last physical block is the trash block for free rows
        self.cache = model.init_paged_cache(
            n_rows, n_blocks + 1, block_size, max_len, dtype=dtype)
        self._trash = n_blocks
        self._table = np.full((n_rows, self.nb_logical), self._trash,
                              np.int32)
        self._free_rows = list(range(n_rows))[::-1]   # pop() -> row 0 first
        self._free_blocks = list(range(n_blocks))[::-1]
        self._owed: dict[int, int] = {}      # row -> reserved, not granted
        self._blocks: dict[int, list[int]] = {}   # row -> granted physical
        self._len: dict[int, int] = {}       # row -> next write position
        self._dirty = True                   # host table ahead of device

    # -- bookkeeping ----------------------------------------------------
    @property
    def n_slots(self) -> int:
        """Batch-row count (scheduler-facing alias: the decode batch is
        one token column per row, same as the dense pool)."""
        return self.n_rows

    @property
    def free_slots(self) -> int:
        return len(self._free_rows)

    @property
    def occupancy(self) -> int:
        return self.n_rows - len(self._free_rows)

    @property
    def blocks_in_use(self) -> int:
        """Physical blocks granted to live requests (excludes
        reservations not yet materialised and the trash block)."""
        return sum(len(b) for b in self._blocks.values())

    @property
    def blocks_reserved(self) -> int:
        """Blocks promised at admission but not yet granted — held back
        from new admissions so in-flight decodes can always grow."""
        return sum(self._owed.values())

    @property
    def live_tokens(self) -> int:
        """Cache positions actually holding live KV entries."""
        return sum(self._len.values())

    @property
    def utilization(self) -> float:
        """live_tokens / granted capacity — 1.0 means zero internal
        fragmentation (every granted block position holds a live KV)."""
        used = self.blocks_in_use * self.block_size
        return self.live_tokens / used if used else 0.0

    def counts(self) -> dict:
        """Host-side KV counts (the serving spans' attributes): live
        positions, blocks granted, blocks reserved, and the positions
        the pool's usable blocks hold."""
        return {"kv_live": self.live_tokens, "kv_blocks": self.blocks_in_use,
                "kv_reserved": self.blocks_reserved,
                "kv_positions": self.n_blocks * self.block_size}

    # -- admission -------------------------------------------------------
    def try_admit(self, total_len: int) -> int | None:
        """Reserve a row + enough blocks for a ``total_len``-position
        request; returns the row, or ``None`` when the pool cannot
        GUARANTEE the request completes (no free row, or too few
        unreserved blocks).  Conservative by design: over-admitting
        would deadlock decode mid-request on an empty free list.
        Raises if ``total_len`` exceeds the logical horizon (the
        request could never fit; waiting won't help)."""
        if total_len > self.max_len:
            raise ValueError(
                f"request needs {total_len} cache positions but the "
                f"pool's logical horizon is max_len={self.max_len}")
        if not self._free_rows:
            return None
        need = -(-total_len // self.block_size)
        if need > len(self._free_blocks) - self.blocks_reserved:
            return None
        row = self._free_rows.pop()
        self._owed[row] = need
        self._blocks[row] = []
        # NOT in self._len yet: the row joins the decode batch (and
        # prepare_step's grant/advance loop) only at adopt() — between
        # try_admit and adopt its table points at the trash block and
        # its masked decode writes are garbage by design.
        return row

    def _grant(self, row: int) -> None:
        """Materialise one reserved block as ``row``'s next logical
        block (host-side; ``sync`` pushes the table to the device)."""
        if not self._free_blocks:
            raise RuntimeError(
                "no free block for a granted reservation — the "
                "try_admit invariant (reserved <= free) was broken")
        blk = self._free_blocks.pop()
        idx = len(self._blocks[row])
        self._blocks[row].append(blk)
        self._owed[row] = max(0, self._owed[row] - 1)
        self._table[row, idx] = blk
        self._dirty = True

    def prepare_step(self) -> None:
        """Grant every active row the block holding its next write
        position, advance the host-side lengths, and sync the table.
        The scheduler calls this immediately before each batched
        ``decode_step`` — after it returns, no in-flight write can miss
        its block."""
        self.prepare_tokens(1)

    def prepare_tokens(self, n: int) -> None:
        """Multi-token ``prepare_step``: grant every active row the
        blocks covering its next ``n`` write positions (a speculative
        verify writes a whole k-token block per row) and advance the
        host-side lengths by ``n``.  Grants stay within the admission
        reservation — the scheduler clamps k so a row never speculates
        past its admitted ``prompt + max_new_tokens`` need — and
        ``rollback`` returns whatever a rejected draft leaves unused."""
        if n < 1:
            raise ValueError(f"need at least one token, got {n}")
        for row in self._len:
            pos = self._len[row]
            while (pos + n - 1) // self.block_size >= \
                    len(self._blocks[row]):
                self._grant(row)
            self._len[row] = pos + n
        self.sync()

    def rollback(self, new_lens: dict[int, int]) -> None:
        """Truncate rows to ``{row: new_length}`` after a speculative
        verify rejected part of a draft block.

        Three things must round-trip, or speculation would leak:
          * device lengths reset, so the validity mask hides the
            rejected entries (they are overwritten before ever being
            readable again — the next block's writes start at
            ``new_length``);
          * tail blocks past ``ceil(new_length/block_size)`` return to
            the free list AND re-credit the row's reservation
            (``_owed``), keeping the admission invariant — granted +
            owed always covers the row's remaining worst case, and
            ``free - reserved`` seen by ``try_admit`` is exactly what
            it was before the speculative grant;
          * the table tail points back at the trash block, so the
            row's future masked writes can't land in blocks that may
            be re-granted to someone else.
        """
        if not new_lens:
            return
        for row, new_len in new_lens.items():
            if row not in self._blocks:
                raise ValueError(
                    f"rollback of row {row}, which holds no blocks "
                    f"(released, or never admitted)")
            if not (0 <= new_len <= self._len.get(row, 0)):
                raise ValueError(
                    f"rollback of row {row} to length {new_len}, "
                    f"outside [0, {self._len.get(row, 0)}] — rollback "
                    f"only ever truncates")
            keep = -(-new_len // self.block_size)
            tail = self._blocks[row][keep:]
            if tail:
                del self._blocks[row][keep:]
                self._free_blocks.extend(reversed(tail))
                self._owed[row] = self._owed.get(row, 0) + len(tail)
                self._table[row, keep:] = self._trash
                self._dirty = True
            self._len[row] = new_len
        self.cache = _set_lengths(self.cache, new_lens, self._scan)
        self.sync()

    def release(self, row: int) -> None:
        """Free a row: blocks return to the free list, the table row
        points back at the trash block (so the freed row's masked
        decode writes stop landing in blocks about to be re-granted)."""
        if not (0 <= row < self.n_rows):
            raise ValueError(f"row {row} outside pool of {self.n_rows}")
        if row not in self._blocks:
            raise ValueError(f"row {row} double-released")
        self._free_blocks.extend(reversed(self._blocks.pop(row)))
        self._owed.pop(row, None)
        self._len.pop(row, None)
        self._table[row, :] = self._trash
        self._dirty = True
        self._free_rows.append(row)

    # -- cache transfer --------------------------------------------------
    def solo_cache(self):
        """A fresh DENSE batch=1 cache at this pool's logical horizon —
        prefill cannot run against paged state (see
        ``layers.apply_attention``); adoption scatters the dense row
        into blocks."""
        return self.model.init_cache(1, self.max_len, dtype=self.dtype)

    def adopt(self, row: int, solo_cache) -> None:
        """Grant the blocks covering the solo-prefilled prompt and
        scatter its dense KV row into them, bitwise (one scatter per
        position-major leaf of a layer: ``k`` and ``v``, or a latent
        cache's ``latent``).  The row's device length is set from the
        solo cache, so decode continues exactly where the solo path
        stood; the pool's own counters stay."""
        if row not in self._blocks:
            raise ValueError(
                f"row {row} was not admitted (call try_admit first)")
        first = api._first_layer(solo_cache)
        length = int(np.asarray(first["length"]).reshape(-1)[0])
        n_grant = -(-length // self.block_size)
        trace.annotate(blocks=n_grant)
        while len(self._blocks[row]) < n_grant:
            self._grant(row)
        phys = jnp.asarray(self._blocks[row][:n_grant], jnp.int32)

        def put(pool_layer, solo_layer):
            out = dict(pool_layer)
            for key in pool_layer.keys() - {"length", "table"}:
                out[key] = _scatter_blocks(pool_layer[key], solo_layer[key],
                                           phys, self._scan)
            if self._scan:
                out["length"] = pool_layer["length"].at[:, row].set(length)
            else:
                out["length"] = pool_layer["length"].at[row].set(length)
            return out

        layers = self.cache["layers"]
        if self._scan:
            layers = put(layers, solo_cache["layers"])
        else:
            layers = [put(pl, sl)
                      for pl, sl in zip(layers, solo_cache["layers"])]
        self.cache = {**self.cache, "layers": layers}
        self._len[row] = length      # joins prepare_step's advance loop
        self.sync()

    def sync(self) -> None:
        """Push the host-side master block table into every layer's
        ``table`` leaf (all layers share one table).  No-op when the
        device copy is current."""
        if not self._dirty:
            return
        t = jnp.asarray(self._table)
        layers = self.cache["layers"]
        if self._scan:
            layers["table"] = jnp.broadcast_to(
                t, layers["table"].shape)
        else:
            for ld in layers:
                ld["table"] = t
        self._dirty = False


def cache_bytes_per_slot(model, max_len: int, dtype=jnp.float32) -> int:
    """Bytes one slot (batch row) of the KV cache occupies — computed
    from ``init_cache`` shapes via eval_shape, no allocation."""
    cfg = model.cfg
    shapes = jax.eval_shape(
        lambda: api.init_cache(cfg, 1, max_len, dtype))
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(shapes))


def suggest_slots(model, plan, max_len: int, *,
                  sram_capacity_bytes: int = 64 << 20,
                  dtype=jnp.float32, max_slots: int = 64) -> int:
    """KV slots that fit beside the plan's SRAM-resident weights.

    The placement plan already commits SRAM to the ReBranch cores and to
    any full-SRAM sites (``PlanStats.branch_bits + sram_bits``); the KV
    pool lives in the remainder of the die's SRAM capacity.  Always at
    least 1 (a pool that can't hold one request isn't a pool), at most
    ``max_slots`` (scheduler batches past ~64 rows want sharding, not a
    wider pool).
    """
    per_slot = cache_bytes_per_slot(model, max_len, dtype)
    resident = 0
    if plan is not None:
        stats = plan.stats(model.cfg)
        resident = (stats.branch_bits + stats.sram_bits) // 8
    budget = max(0, sram_capacity_bytes - resident)
    return max(1, min(max_slots, budget // per_slot))


def suggest_paged(model, plan, max_len: int, *,
                  sram_capacity_bytes: int = 64 << 20,
                  dtype=jnp.float32, max_rows: int = 64,
                  block_size: int | None = None) -> tuple[int, int, int]:
    """(n_rows, n_blocks, block_size) for a :class:`PagedPool` in the
    SAME byte budget :func:`suggest_slots` would spend on dense rows.

    The block size is derived from :func:`cache_bytes_per_slot`: one
    dense slot costs ``per_slot`` bytes over ``max_len`` positions, so a
    block of ``block_size`` positions costs
    ``per_slot * block_size / max_len`` — the budget divided by that is
    the block count.  Default block size is ``max_len // 8`` clamped to
    [8, 64] and rounded to a divisor of ``max_len`` (the paged view
    must keep the dense attention geometry).  Rows are sized so the
    pool can hold ``2x`` the dense slot count of all-half-length
    requests — the fragmentation win paging exists for — capped at
    ``max_rows``.
    """
    dense = suggest_slots(model, plan, max_len,
                          sram_capacity_bytes=sram_capacity_bytes,
                          dtype=dtype, max_slots=max_rows)
    if block_size is None:
        block_size = min(64, max(8, max_len // 8))
        while max_len % block_size:
            block_size -= 1
    if max_len % block_size:
        raise ValueError(
            f"block_size {block_size} does not divide max_len {max_len}")
    blocks_per_slot = max_len // block_size
    n_blocks = max(blocks_per_slot, dense * blocks_per_slot)
    n_rows = max(1, min(max_rows, 2 * dense))
    return n_rows, n_blocks, block_size

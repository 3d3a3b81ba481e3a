"""RouterModel — the flagship device program: match → compact → fan-out.

One jittable step replaces the reference's entire per-message read path
``emqx_router:match_routes/1`` → ``emqx_trie:match/1`` → subscriber-table
lookups → pid fan-out loop (emqx_router.erl:141-157,
emqx_broker.erl:546-579) with a single batched XLA program over HBM-
resident tables:

    tokens [B, L] ──trie match──► cand [B, S] ──compact──► fids [B, M]
                                                  │
          dense pool [P, W] + rowmap [F] ──OR────►└─► fanout [B, W], counts

Fan-out is HYBRID (the emqx_broker_helper.erl:55,82-92 sharding
discipline, TPU-shaped): subscriber slots are a FIXED shard space
(SlotRegistry hashes past capacity), per-filter slot sets live host-side
in a refcounted dict, and only HIGH-degree filters (broadcast topics,
degree > dense_threshold) get a row in the device dense pool — the OR
aggregation is exactly the regime where it pays.  A dense [F, W] bitmap
would cost 16 GB at 10M filters (round-1 weak #2, BASELINE config 3);
the pool costs P·W for the few filters that need it, and the structures
never grow with subscriber count.

Sharding (see emqx_tpu.parallel.mesh): match runs with B over the full
dp×tp mesh; fids then reshard to dp-only (XLA inserts an all-gather of the
small [B, M] tensor along tp) so fan-out can keep W sharded over tp.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from emqx_tpu.ops import fanout as fo
from emqx_tpu.ops import trie_match as tm
from emqx_tpu.parallel import mesh as pmesh
from emqx_tpu.router.index import ShardedTrieIndex, TrieIndex


def router_step(
    trie: tm.DeviceTrie,
    rowmap: jax.Array,
    pool: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
    sys_flags: jax.Array,
    *,
    K: int = 32,
    M: int = 128,
    max_probes: int = 8,
    ret_cap: Optional[int] = None,
    shardings: Optional[dict[str, NamedSharding]] = None,
    with_counters: bool = False,
):
    """The full publish-batch routing step (pure, jittable).

    Returns (fids [B, ret_cap or M], fanout [B, W], overflow [B],
    fan_any [], counters); fanout covers the dense-pool (high-degree)
    filters, low-degree slots decode host-side from the subscription
    dict.

    ``ret_cap`` trims the RETURNED fid columns: device→host transfer is
    a large share of the serving path's cost (a round trip and bandwidth
    per flush), and mean matches/topic is ~1.7 against M=128
    buffered columns. Topics matching more than ret_cap filters are
    flagged overflow and take the host-oracle fallback upstream —
    correctness never depends on the trim. ``fan_any`` (scalar) lets the
    host skip fetching the [B, W] fanout block entirely when no
    dense-pool row matched (the common case below the dense threshold).

    ``with_counters`` adds the kernel-plane counters vector (ISSUE 18):
    a [C] int32 pack in tm.KERNEL_COUNTER_FIELDS order, computed by the
    same program with elementwise reductions and fetched in the SAME
    publish_batch_collect device_get — no extra sync. ``counters`` is
    None when disabled (a dropped pytree leaf, so callers unpack a
    5-tuple either way). The ret_cap trim's spill is NOT a counter — it
    rides ``overflow`` into the broker's fallback/ledger seam.
    """
    cand, overflow, mstats = tm.match_batch(
        trie, tokens, lengths, sys_flags, K=K, max_probes=max_probes
    )
    fids, truncated = tm.compact_fids(cand, M=M)
    counters = None
    if with_counters:
        occ = jnp.sum((fids >= 0).astype(jnp.int32), axis=1)   # [B]
        counters = tm.pack_counters(
            frontier_peak=mstats["frontier_peak"],
            probe_iters=mstats["probe_iters"],
            cand_pre=mstats["cand_pre"],
            cand_post=jnp.sum(occ),
            compact_peak=jnp.max(occ),
            overflow_rows=mstats["overflow_rows"],
            trunc_rows=jnp.sum(truncated.astype(jnp.int32)),
        )
    if shardings is not None:
        # reshard the compacted fids to dp-only before the tp-sharded OR
        fids = jax.lax.with_sharding_constraint(fids, shardings["batch_dp"])
    out = fo.fanout_pool(rowmap, pool, fids)
    if shardings is not None:
        out = jax.lax.with_sharding_constraint(out, shardings["fanout_out"])
    fan_any = jnp.any(out != 0)
    overflow = overflow | truncated
    if ret_cap is not None and ret_cap < M:
        overflow = overflow | (jnp.sum(fids >= 0, axis=1) > ret_cap)
        fids = fids[:, :ret_cap]
    return fids, out, overflow, fan_any, counters


def router_step_sharded(
    trie: tm.DeviceTrie,   # fields [S, H] / [S, N] — shard axis over tp
    rowmap: jax.Array,
    pool: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
    sys_flags: jax.Array,
    *,
    n_shards: int,
    K: int = 32,
    M: int = 128,
    max_probes: int = 8,
    ret_cap: Optional[int] = None,
    shardings: Optional[dict[str, NamedSharding]] = None,
    with_counters: bool = False,
):
    """The routing step over a subscription-sharded trie.

    Layout: the trie's shard axis is partitioned over ``tp`` (each
    device holds its fid-range slice), the topic batch over ``dp`` only
    (tp-replicated — every shard must see every topic).  Each shard
    matches and compacts its own slice to M shard-local fids, local
    fids translate to the interleaved global namespace, and the [B,
    S·M] shard-major merge is the ONLY tensor the tp collective moves —
    compacted ids, never the [S, B, (L+1)·2K] candidate block and never
    the bitmaps.  After the merge the step is exactly ``router_step``:
    one more compact, then the tp-sharded dense-pool OR over GLOBAL
    fids.

    n_shards=1 degenerates bit-identically to ``router_step`` on the
    flat trie (identity fid translation, no-op second compact).

    ``with_counters`` packs a PER-SHARD [S, C] counters block (tm.
    KERNEL_COUNTER_FIELDS order): match-side fields come per shard from
    the vmapped walk, compact-side fields from each shard's own M
    compact (pre-merge — the shard-skew signal).  The merged second
    compact's spill rides ``overflow`` to the broker fallback seam, not
    the counters.
    """
    cand, overflow, mstats = tm.match_batch_sharded(
        trie, tokens, lengths, sys_flags, K=K, max_probes=max_probes
    )
    S, B, _ = cand.shape
    per, trunc = jax.vmap(lambda c: tm.compact_fids(c, M=M))(cand)
    counters = None
    if with_counters:
        occ = jnp.sum((per >= 0).astype(jnp.int32), axis=2)    # [S, B]
        counters = tm.pack_counters(
            frontier_peak=mstats["frontier_peak"],
            probe_iters=mstats["probe_iters"],
            cand_pre=mstats["cand_pre"],
            cand_post=jnp.sum(occ, axis=1),
            compact_peak=jnp.max(occ, axis=1),
            overflow_rows=mstats["overflow_rows"],
            trunc_rows=jnp.sum(trunc.astype(jnp.int32), axis=1),
        )
    shard_ids = jnp.arange(S, dtype=per.dtype)[:, None, None]
    per = jnp.where(per >= 0, per * n_shards + shard_ids, -1)
    merged = jnp.moveaxis(per, 0, 1).reshape(B, S * M)
    if shardings is not None:
        # the tp all-gather: [B, S*M] compacted global fids to dp-only
        merged = jax.lax.with_sharding_constraint(
            merged, shardings["batch_dp"])
    fids, trunc2 = tm.compact_fids(merged, M=M)
    truncated = jnp.any(trunc, axis=0) | trunc2
    out = fo.fanout_pool(rowmap, pool, fids)
    if shardings is not None:
        out = jax.lax.with_sharding_constraint(out, shardings["fanout_out"])
    fan_any = jnp.any(out != 0)
    overflow = overflow | truncated
    if ret_cap is not None and ret_cap < M:
        overflow = overflow | (jnp.sum(fids >= 0, axis=1) > ret_cap)
        fids = fids[:, :ret_cap]
    return fids, out, overflow, fan_any, counters


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _apply_patches(trie: tm.DeviceTrie, rowmap: jax.Array, pool: jax.Array,
                   tupd: dict, rowmap_upd: tuple, pool_upd: tuple) -> tuple:
    """ONE dispatch applying every pending element update to the donated
    HBM buffers (XLA reuses the donated allocations, so the work is
    O(#updates), not O(table); one launch keeps the subscribe→routable
    path at a single host→device round trip)."""
    new = {}
    for name in tm.DeviceTrie._fields:
        arr = getattr(trie, name)
        # idx is a 1-D index array (flat trie) or a (shard_idx, elem_idx)
        # pair (sharded [S, ...] trie) — .at[] takes both
        idx, vals = tupd[name]
        new[name] = arr.at[idx].set(vals)
    ridx, rvals = rowmap_upd
    rows, cols, vals = pool_upd
    return (tm.DeviceTrie(**new), rowmap.at[ridx].set(rvals),
            pool.at[rows, cols].set(vals))


# the _apply_patches pad ladder: a 4×-stepped set so the jit compiles a
# handful of variants total (per-array pow2 pads would make the cross
# product of shapes explode into a fresh ~100ms compile almost every
# refresh — measured). A drain larger than the top rung re-uploads the
# tables instead, so RouterModel.warm() covers every patch shape.
PATCH_BUCKETS = (64, 256, 1024, 4096)


def _patch_bucket(n: int) -> int:
    """Shared pad size for ALL update vectors of one _apply_patches call."""
    return next(cap for cap in PATCH_BUCKETS if cap >= n)


def _batch_bucket(n: int) -> int:
    """Pad a batch to a pow2 bucket (≥64) — keeps the set of compiled
    program shapes small, the {active,N}-style batching discipline."""
    B = 64
    while B < n:
        B *= 2
    return B


class ColdTables(RuntimeError):
    """A ``compiled_only`` submit would compile: the device tables grew
    (or the batch outgrew the warmed buckets) since the last ``warm()``.
    Nothing was launched."""


def _pad_to(cap: int, idx: np.ndarray, vals: np.ndarray):
    """Pad update vectors to cap by repeating the first element —
    a duplicate scatter of an identical value is a no-op."""
    pad = cap - len(idx)
    return (np.concatenate([idx, np.repeat(idx[:1], pad)]),
            np.concatenate([vals, np.repeat(vals[:1], pad)]))


class _HostMatcher:
    """CPU-platform serving path: an exact host matcher keyed by fid.

    The round-5 CPU bench measured the XLA kernel at 11.9k topics/s on
    CPU against 2.07M/s for the C++ SubTable on the same box — a 0.1x
    ``vs_host_oracle`` regression the model used to serve whenever the
    resolved platform was cpu.  When active (see
    ``RouterModel._resolve_host_dispatch``) ``publish_batch`` routes
    through this mirror instead of dispatching the XLA program.

    Backend: the C++ ``NativeSubTable`` (owner = fid) when the native
    plane built, else the pure-python host-oracle ``Trie``.  Entries are
    guarded by a fid→filter dict so refcount drift in either backend is
    impossible (adds/removes are idempotent per fid).
    """

    def __init__(self) -> None:
        self._fids: dict[int, str] = {}
        self._native = None
        self._trie = None
        self._by_filt: dict[str, int] = {}
        from emqx_tpu import native
        if native.available():
            self._native = native.NativeSubTable()
        else:
            from emqx_tpu.router.trie import Trie
            self._trie = Trie()
        self.backend = "native" if self._native is not None else "oracle"

    def add(self, fid: int, filt: str) -> None:
        if fid in self._fids:
            return
        self._fids[fid] = filt
        if self._native is not None:
            self._native.add(fid, filt)
        else:
            self._trie.insert(filt)
            self._by_filt[filt] = fid

    def remove(self, fid: int) -> None:
        filt = self._fids.pop(fid, None)
        if filt is None:
            return
        if self._native is not None:
            self._native.remove(fid, filt)
        else:
            self._trie.delete(filt)
            self._by_filt.pop(filt, None)

    def match(self, topic: str) -> list[int]:
        if self._native is not None:
            fids = list(self._native.match(topic))
        else:
            fids = [self._by_filt[f] for f in self._trie.match(topic)
                    if f in self._by_filt]
        if topic.startswith("$"):
            # MQTT-3.7.2-1: a root-level wildcard must not match a
            # $-topic.  The oracle Trie enforces this itself; the C++
            # SubTable does not, so filter uniformly here (matches the
            # device kernel's sys_block lane kill at level 0)
            fids = [f for f in fids
                    if self._fids[f].split("/", 1)[0] not in ("+", "#")]
        return fids

    def close(self) -> None:
        if self._native is not None:
            self._native.close()
            self._native = None


class RouterModel:
    """Host wrapper: TrieIndex + subscriber bitmaps + the jitted step.

    The broker layer registers subscribers into per-filter bitmap rows
    (slot = subscriber id from the connection manager); ``publish_batch``
    tokenizes topics, runs the device step, and reports matches.

    Mutations are applied to the device arrays *incrementally*: the
    TrieIndex patches its host arrays in place and records dirty indices;
    ``refresh`` scatters just those elements into HBM with donated jits
    (subscribe→routable is O(topic-depth)).  A full re-upload happens
    only when the index signals structural growth (``needs_rebuild``) or
    the bitmap capacity changes — the emqx_trie.erl:113-144 incremental
    insert/delete semantics, device-resident.
    """

    def __init__(
        self,
        index: Optional[Union[TrieIndex, ShardedTrieIndex]] = None,
        *,
        n_sub_slots: int = 8192,
        K: int = 32,
        M: int = 128,
        ret_cap: int = 16,
        dense_threshold: int = 64,
        mesh: Optional[Mesh] = None,
        trie_shards: Optional[int] = None,
        kernel_telemetry: Optional[bool] = None,
    ) -> None:
        if index is None:
            index = (ShardedTrieIndex(trie_shards) if trie_shards
                     else TrieIndex())
        elif trie_shards is not None and (
                getattr(index, "n_shards", 1) != trie_shards):
            raise ValueError(
                f"trie_shards={trie_shards} conflicts with the supplied "
                f"index ({getattr(index, 'n_shards', 1)} shard(s))")
        self.index = index
        self._sharded = isinstance(index, ShardedTrieIndex)
        self.n_shards = index.n_shards if self._sharded else 1
        self.n_sub_slots = n_sub_slots
        self.K, self.M = K, M
        self.ret_cap = min(ret_cap, M)
        self.dense_threshold = dense_threshold
        self.mesh = mesh
        self.shardings = pmesh.router_shardings(mesh) if mesh else None
        if self._sharded and mesh is not None:
            tp_ext = mesh.shape[pmesh.TP]
            if self.n_shards % tp_ext:
                raise ValueError(
                    f"trie shard count {self.n_shards} must be a multiple "
                    f"of the tp mesh extent {tp_ext} — the stacked [S, ...]"
                    f" buffers partition their shard axis evenly over tp")
        # fid → {slot: refcount} — slots are SHARDS (SlotRegistry may
        # hash many sids into one), so a slot stays set while any local
        # subscriber of the filter lives in it
        self._subs: dict[int, dict[int, int]] = {}
        # fid → refcount for AUXILIARY filters (rule-engine FROM filters
        # co-batched with router match, BASELINE config 5): they live in
        # the same device trie but own no subscriber slots; the batch
        # decode reports them separately so fan-out and rule matching
        # both ride one kernel launch (emqx_rule_engine.erl:198-205)
        self._aux_refs: dict[int, int] = {}
        # fid-indexed bool masks mirroring _subs/_aux_refs membership:
        # the batch decode classifies whole [B, M] fid blocks with two
        # vectorized gathers instead of per-fid dict lookups
        self._sub_mask = np.zeros(64, bool)
        self._aux_mask = np.zeros(64, bool)
        # high-degree filters promoted into the device dense pool
        self._dense_row: dict[int, int] = {}      # fid → pool row
        self._row_free: list[int] = []
        self._next_row = 0
        # One lock over index mutation, pending-update drain, device
        # refresh AND the step launch: subscribes arrive on the server's
        # event-loop thread while the pipeline flushes on a worker
        # thread — an unsynchronized drain could scatter a half-applied
        # insert (torn trie) into HBM, and a refresh mid-launch would
        # donate away buffers the step still reads.  The serialization
        # mirrors the reference's per-topic router_pool discipline
        # (emqx_router.erl:200-204) at model granularity.
        self._mlock = threading.RLock()
        self._trie_dev: Optional[tm.DeviceTrie] = None
        self._rowmap_dev: Optional[jax.Array] = None
        self._pool_dev: Optional[jax.Array] = None
        self._rowmap_host: Optional[np.ndarray] = None  # [F_cap] int32
        self._pool_host: Optional[np.ndarray] = None    # [P_cap, W] uint32
        self._rowmap_dirty: set[int] = set()
        self._pool_dirty: set[tuple[int, int]] = set()  # (row, word)
        self._dirty = True
        self.upload_count = 0      # full device uploads (test/obs hook)
        self.patch_count = 0       # incremental scatter flushes
        self.launch_count = 0      # publish_batch kernel launches
        self.host_match_count = 0  # batches served by the host matcher
        # kernel-plane observability (ISSUE 18): with_counters bakes the
        # [*, C] counters pack into the step so it rides the SAME
        # collect-time device_get; EMQX_TPU_KERNEL_TELEMETRY=0 is the
        # escape hatch (compiles the counters out entirely)
        if kernel_telemetry is None:
            kernel_telemetry = os.environ.get(
                "EMQX_TPU_KERNEL_TELEMETRY", "1"
            ).lower() not in ("0", "off", "false")
        self.kernel_telemetry = bool(kernel_telemetry)
        # DeviceMetricsFold attach point (observe/device_metrics.py);
        # the model never imports the observe plane — the app wires it
        self.telemetry = None
        self.patch_upload_bytes = 0   # unpadded dirty bytes scattered
        # what warm() compiled for: table shapes and the top batch bucket
        self._warm_shapes: Optional[tuple] = None
        self._warm_batch = 0
        if self._sharded:
            step_fn = functools.partial(
                router_step_sharded, n_shards=self.n_shards)
        else:
            step_fn = router_step
        self._step = jax.jit(
            functools.partial(
                step_fn,
                K=K,
                M=M,
                ret_cap=self.ret_cap,
                max_probes=self.index.max_probes,
                shardings=self.shardings,
                with_counters=self.kernel_telemetry,
            )
        )
        # platform-aware dispatch: on a cpu backend the XLA kernel is a
        # ~0.1x regression vs the host matcher (round-5 CPU bench), so serve
        # from the host mirror unless the escape hatch says otherwise
        self._host_matcher = (_HostMatcher()
                              if self._resolve_host_dispatch() else None)

    def _resolve_host_dispatch(self) -> bool:
        """Should publish_batch serve from the host matcher?

        ``EMQX_TPU_CPU_KERNEL``: ``host`` forces the host matcher,
        ``xla`` forces the device kernel (the bench's validation-mode
        escape hatch — measuring the XLA program ON cpu is the point
        there), anything else is auto: host matcher iff the resolved
        platform is cpu and no mesh was requested.
        """
        mode = os.environ.get("EMQX_TPU_CPU_KERNEL", "auto").lower()
        if mode == "host":
            return True
        if mode == "xla":
            return False
        return self.mesh is None and jax.default_backend() == "cpu"

    # -- subscription surface (driven by the broker layer) -----------------

    def _mask_of(self, name: str, n: int) -> np.ndarray:
        """The named fid mask, grown to cover at least ``n`` fids."""
        mask = getattr(self, name)
        if mask.shape[0] < n:
            mask = np.pad(mask, (0, n - mask.shape[0]))
            setattr(self, name, mask)
        return mask

    def _mark(self, mask_name: str, fid: int, val: bool) -> None:
        mask = getattr(self, mask_name)
        if fid >= mask.shape[0]:
            grown = np.zeros(max(fid + 1, mask.shape[0] * 2), bool)
            grown[: mask.shape[0]] = mask
            mask = grown
            setattr(self, mask_name, mask)
        mask[fid] = val

    def subscribe(self, filt: str, slot: int) -> int:
        if not 0 <= slot < self.n_sub_slots:
            raise ValueError(
                f"subscriber slot {slot} out of range [0, {self.n_sub_slots})"
            )
        with self._mlock:
            fid = self.index.insert(filt)
            if self._host_matcher is not None:
                self._host_matcher.add(fid, filt)
            self._mark("_sub_mask", fid, True)
            slots = self._subs.setdefault(fid, {})
            n = slots.get(slot, 0)
            slots[slot] = n + 1
            if n == 0:                     # first subscriber in the shard
                self._slot_added(fid, slot)
                self._dirty = True
            return fid

    def unsubscribe(self, filt: str, slot: int) -> None:
        with self._mlock:
            fid = self.index.fid_of(filt)
            if fid is None:
                return
            slots = self._subs.get(fid)
            if not slots or slot not in slots:
                return
            slots[slot] -= 1
            if slots[slot] == 0:
                del slots[slot]
                self._slot_removed(fid, slot)
                if not slots:
                    self._subs.pop(fid, None)
                    self._mark("_sub_mask", fid, False)
                    # an aux registration (rule FROM filter) keeps the
                    # trie entry alive past the last subscriber
                    if fid not in self._aux_refs:
                        self.index.delete(filt)
                        if self._host_matcher is not None:
                            self._host_matcher.remove(fid)
                self._dirty = True

    # -- auxiliary (rule-engine) filters ------------------------------------

    def aux_register(self, filt: str) -> int:
        """Co-batch a non-subscriber filter (rule FROM clause) into the
        device trie; refcounted across rules sharing a filter."""
        with self._mlock:
            fid = self.index.insert(filt)
            if self._host_matcher is not None:
                self._host_matcher.add(fid, filt)
            self._aux_refs[fid] = self._aux_refs.get(fid, 0) + 1
            self._mark("_aux_mask", fid, True)
            self._dirty = True
            return fid

    def aux_release(self, filt: str) -> None:
        with self._mlock:
            fid = self.index.fid_of(filt)
            if fid is None or fid not in self._aux_refs:
                return
            self._aux_refs[fid] -= 1
            if self._aux_refs[fid] > 0:
                return
            del self._aux_refs[fid]
            self._mark("_aux_mask", fid, False)
            if fid not in self._subs:      # no subscribers either
                self.index.delete(filt)
                if self._host_matcher is not None:
                    self._host_matcher.remove(fid)
            self._dirty = True

    # -- dense-pool promotion / demotion -----------------------------------

    def _slot_added(self, fid: int, slot: int) -> None:
        row = self._dense_row.get(fid)
        if row is not None:
            self._pool_bit(row, slot, on=True)
        elif len(self._subs[fid]) > self.dense_threshold:
            self._promote(fid)

    def _slot_removed(self, fid: int, slot: int) -> None:
        row = self._dense_row.get(fid)
        if row is not None:
            self._pool_bit(row, slot, on=False)
            # hysteresis: demote well below the promote threshold so a
            # filter oscillating around it doesn't thrash the pool
            if len(self._subs[fid]) < self.dense_threshold // 2:
                self._demote(fid)

    def _promote(self, fid: int) -> None:
        if self._row_free:
            row = self._row_free.pop()
        else:
            row = self._next_row
            self._next_row += 1
        self._dense_row[fid] = row
        if (self._pool_host is None or row >= self._pool_host.shape[0]):
            self._pool_host = None        # pool growth → full rebuild
        else:
            for slot in self._subs[fid]:
                self._pool_bit(row, slot, on=True)
        self._set_rowmap(fid, row)

    def _demote(self, fid: int) -> None:
        row = self._dense_row.pop(fid)
        if self._pool_host is not None and row < self._pool_host.shape[0]:
            for slot in self._subs.get(fid, ()):   # leave the row zeroed
                self._pool_bit(row, slot, on=False)
        self._row_free.append(row)
        self._set_rowmap(fid, -1)

    def _pool_bit(self, row: int, slot: int, *, on: bool) -> None:
        pool = self._pool_host
        if pool is None or row >= pool.shape[0] or slot // 32 >= pool.shape[1]:
            self._pool_host = None
            return
        if on:
            pool[row, slot // 32] |= np.uint32(1) << np.uint32(slot % 32)
        else:
            pool[row, slot // 32] &= ~(np.uint32(1) << np.uint32(slot % 32))
        self._pool_dirty.add((row, slot // 32))

    def _set_rowmap(self, fid: int, row: int) -> None:
        rm = self._rowmap_host
        if rm is None or fid >= rm.shape[0]:
            self._rowmap_host = None      # fid capacity growth → rebuild
            return
        rm[fid] = row
        self._rowmap_dirty.add(fid)

    # -- device refresh ----------------------------------------------------

    @property
    def bitmap_words(self) -> int:
        return max(1, (self.n_sub_slots + 31) // 32)

    def build_pool(self) -> tuple[np.ndarray, np.ndarray]:
        """Full (rowmap, pool) rebuild: compact rows, fresh headroom."""
        W = self.bitmap_words
        live = max(1, len(self.index.filters))
        F = 64
        while F < live + live // 2:
            F *= 2
        rowmap = np.full(F, -1, np.int32)
        # compact row ids (frees fragmentation from demotes)
        self._dense_row = {
            fid: i for i, fid in enumerate(sorted(self._dense_row))
        }
        self._row_free = []
        self._next_row = len(self._dense_row)
        P = 64
        while P < max(1, self._next_row * 2):
            P *= 2
        pool = np.zeros((P, W), np.uint32)
        for fid, row in self._dense_row.items():
            rowmap[fid] = row
            for slot in self._subs.get(fid, ()):
                pool[row, slot // 32] |= np.uint32(1) << np.uint32(slot % 32)
        return rowmap, pool

    def refresh(self) -> None:
        """Bring the device arrays up to date: one fused scatter dispatch
        when possible, full upload on structural growth."""
        with self._mlock:
            self._refresh_locked()

    def _upload_pool(self) -> None:
        rowmap, pool = self._rowmap_host, self._pool_host
        if self.shardings is not None:
            rowmap = jax.device_put(rowmap, self.shardings["replicated"])
            pool = jax.device_put(pool, self.shardings["bitmaps"])
        else:
            rowmap, pool = jnp.asarray(rowmap), jnp.asarray(pool)
        self._rowmap_dev, self._pool_dev = rowmap, pool
        self._rowmap_dirty.clear()
        self._pool_dirty.clear()

    def _patch_args(self, cap: int, updates: dict, rm_dirty: list,
                    pool_dirty: list) -> tuple:
        """The ``_apply_patches`` update operands, every vector padded to
        ``cap`` (empty inputs become no-op self-writes)."""
        tupd = {}
        for name in tm.DeviceTrie._fields:
            idxs = updates.get(name)
            if self._sharded:
                # (shard, idx) pairs → a 2-D scatter into [S, ...]:
                # a steady-state subscribe patches just the owning
                # shard's slice, never the whole stack
                if idxs:
                    sidx = np.asarray([s for s, _ in idxs], np.int32)
                    eidx = np.asarray([i for _, i in idxs], np.int32)
                else:
                    sidx = np.zeros(1, np.int32)   # no-op self-write
                    eidx = np.zeros(1, np.int32)
                shards = self.index.shards
                vals = np.asarray(
                    [getattr(shards[s].arrays, name)[i]
                     for s, i in zip(sidx, eidx)], np.int32)
                sidx, vals = _pad_to(cap, sidx, vals)
                eidx, _ = _pad_to(cap, eidx, eidx)
                tupd[name] = ((jnp.asarray(sidx), jnp.asarray(eidx)),
                              jnp.asarray(vals))
                continue
            host = getattr(self.index.arrays, name)
            if idxs:
                idx = np.asarray(idxs, np.int32)
            else:
                idx = np.zeros(1, np.int32)    # no-op self-write
            vals = host[idx]
            idx, vals = _pad_to(cap, idx, vals)
            tupd[name] = (jnp.asarray(idx), jnp.asarray(vals))
        ridx = (np.asarray(rm_dirty, np.int32) if rm_dirty
                else np.zeros(1, np.int32))
        rvals = self._rowmap_host[ridx]
        ridx, rvals = _pad_to(cap, ridx, rvals)
        if pool_dirty:
            rows = np.asarray([r for r, _ in pool_dirty], np.int32)
            cols = np.asarray([c for _, c in pool_dirty], np.int32)
        else:
            rows = np.zeros(1, np.int32)
            cols = np.zeros(1, np.int32)
        vals = self._pool_host[rows, cols]
        # pad rows/cols/vals with the SAME (row0, col0, val0) triple:
        # a duplicate write of the identical value is a no-op
        rows, vals = _pad_to(cap, rows, vals)
        cols, _ = _pad_to(cap, cols, cols)
        return (tupd, (jnp.asarray(ridx), jnp.asarray(rvals)),
                (jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals)))

    def _refresh_locked(self) -> None:
        # fid capacity must cover every live fid (rowmap gathers by fid)
        if (self._rowmap_host is not None
                and len(self.index.filters) > self._rowmap_host.shape[0]):
            self._rowmap_host = None
        full_pool = (self._pool_host is None or self._rowmap_host is None
                     or self._pool_dev is None
                     or self._pool_host.shape[1] != self.bitmap_words)
        full_trie = (self.index.needs_rebuild or self._trie_dev is None
                     or (not self._sharded and self.index.arrays is None))
        updates = {} if full_trie else self.index.drain_updates()
        if updates and (full_pool or max(map(len, updates.values()))
                        > PATCH_BUCKETS[-1]):
            # a pool rebuild changes the table shapes, and a drain past
            # the top patch rung has no warmed scatter: re-upload the
            # host arrays rather than compile a scatter for them
            full_trie, updates = True, {}
        if full_trie:
            if self._sharded:
                # ensure() also equalizes the per-shard edge-table sizes
                # so the [S, H] stack shares one probe mask
                shard_arrays = self.index.ensure()
                trie_dev = tm.stacked_device_trie(shard_arrays)
                if self.shardings is not None:
                    trie_dev = jax.device_put(
                        trie_dev, self.shardings["trie_sub"])
                else:
                    trie_dev = tm.DeviceTrie(
                        *(jnp.asarray(x) for x in trie_dev))
            else:
                arrays = self.index.ensure()
                trie_dev = tm.device_trie(arrays)
                if self.shardings is not None:
                    trie_dev = jax.device_put(
                        trie_dev, self.shardings["replicated"])
            self._trie_dev = trie_dev
            self.index.drain_updates()    # superseded by the upload
            self.upload_count += 1

        if full_pool:
            self._rowmap_host, self._pool_host = self.build_pool()
            self._upload_pool()
        elif max(len(self._rowmap_dirty),
                 len(self._pool_dirty)) > PATCH_BUCKETS[-1]:
            self._upload_pool()    # same shapes, no unwarmed scatter

        rm_dirty = [] if full_pool else sorted(self._rowmap_dirty)
        pool_dirty = [] if full_pool else sorted(self._pool_dirty)
        if updates or rm_dirty or pool_dirty:
            # patch-upload accounting (UNPADDED dirty counts — the pad
            # repeats a no-op write): each trie element scatters an
            # (index, value) int32 pair, +4 B for the shard index on the
            # stacked layout; pool writes carry (row, col, val)
            n_elems = sum(len(v) for v in updates.values())
            self.patch_upload_bytes += (
                n_elems * (12 if self._sharded else 8)
                + len(rm_dirty) * 8 + len(pool_dirty) * 12)
            cap = _patch_bucket(max(
                max((len(v) for v in updates.values()), default=0),
                len(rm_dirty), len(pool_dirty)))
            self._trie_dev, self._rowmap_dev, self._pool_dev = \
                _apply_patches(
                    self._trie_dev, self._rowmap_dev, self._pool_dev,
                    *self._patch_args(cap, updates, rm_dirty, pool_dirty))
            self._rowmap_dirty.clear()
            self._pool_dirty.clear()
            self.patch_count += 1
        self._dirty = False

    # -- the hot path ------------------------------------------------------

    def publish_batch(self, topics: Sequence[str]):
        """Route a batch of publish topics.

        Returns ``(matched, aux, slots, fallback)``:
        - matched: per-topic subscriber filter strings
        - aux: per-topic auxiliary (rule FROM) filter strings matched by
          the same kernel launch — config-5 co-batching
        - slots: per-topic subscriber shard slots
        - fallback: batch positions (overflow/too-long) that must take
          the host-oracle path upstream (router.match_filters)
        """
        return self.publish_batch_collect(self.publish_batch_submit(topics))

    def publish_batch_submit(self, topics: Sequence[str], *,
                             compiled_only: bool = False):
        """Stage 1: tokenize + dispatch the kernel; returns an opaque
        pending handle WITHOUT waiting for the device. The serving
        pipeline overlaps this launch's device round trip with the NEXT
        batch's hook fold and tokenization — the SURVEY §2.5-6
        double-buffering.

        ``compiled_only`` (the device lane, whose parked frames have a
        deadline) raises ColdTables instead of launching a program that
        ``warm()`` has not compiled."""
        if self._host_matcher is not None:
            # cpu platform: serve synchronously from the host matcher —
            # the "pending" handle is the finished result, so the
            # pipeline's submit/collect overlap degenerates harmlessly
            return ("host", self._publish_batch_host(topics))
        t0 = time.monotonic_ns()
        with self._mlock:
            if self._dirty or self._trie_dev is None:
                self._refresh_locked()
            if compiled_only and (
                    self._warm_shapes != self._table_shapes()
                    or len(topics) > self._warm_batch):
                raise ColdTables(
                    f"batch {len(topics)} / tables {self._table_shapes()}"
                    f" not compiled")
            self.launch_count += 1
            n = len(topics)
            args, too_long = self._batch_args(topics)
            fids, fanout, overflow, fan_any, counters = self._step(
                self._trie_dev, self._rowmap_dev, self._pool_dev, *args
            )
            # freed fids stay quarantined until this batch is decoded —
            # a reused fid would decode as the WRONG (new) filter
            self.index.begin_inflight()
            # (t0, t1) stamps the submit stage (tokenize + dispatch) for
            # the telemetry fold; the dispatch is async, so t1 is NOT a
            # device sync point
            return (list(topics), [b for b in too_long if b < n], fids,
                    fanout, overflow, fan_any, counters,
                    (t0, time.monotonic_ns()))

    def _batch_args(self, topics: Sequence[str]):
        """(tokens, lengths, sys_flags) for ``topics`` padded to their
        batch bucket and placed for the step, plus the too-long rows."""
        n = len(topics)
        padded = list(topics) + [""] * (_batch_bucket(n) - n)
        tokens, lengths, sys_flags, too_long = self.index.tokenize(padded)
        # padding rows: length 0 + sys flag so even the root '#'/'+'
        # filters (which match an empty prefix) cannot emit for them
        lengths[n:] = 0
        sys_flags[n:] = True
        args = (tokens, lengths, sys_flags)
        if self.shardings is not None:
            # sharded trie: topics go dp-only (tp-REPLICATED — every
            # trie shard matches every topic); replicated trie keeps
            # the full dp×tp batch split
            key = "batch_dp" if self._sharded else "batch_full"
            args = jax.device_put(args, self.shardings[key])
        return args, too_long

    def _table_shapes(self) -> tuple:
        return tuple(x.shape for x in (*self._trie_dev, self._rowmap_dev,
                                       self._pool_dev))

    def warm(self, max_batch: int) -> dict[str, float]:
        """Compile, without running, every program a serving path can
        launch on the current tables: the step at each batch bucket from
        64 to ``max_batch`` and ``_apply_patches`` at each patch bucket.
        Returns seconds per program (``step/B``, ``patch/cap``); a call
        on tables already warm compiles nothing and returns {}.

        The compiles run on shape specs taken under the lock and outside
        it, so subscribes and Python-path publishes are not held for the
        tens of seconds a cold chip compile takes."""
        if self._host_matcher is not None:
            return {}

        def spec(x):
            # an uncommitted array's program is cached under no sharding
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=(
                x.sharding if getattr(x, "committed", False) else None))

        with self._mlock:
            if self._dirty or self._trie_dev is None:
                self._refresh_locked()
            shapes = self._table_shapes()
            if shapes == self._warm_shapes and max_batch <= self._warm_batch:
                return {}
            tables = jax.tree.map(
                spec, (self._trie_dev, self._rowmap_dev, self._pool_dev))
            programs = []
            B = 64
            while True:
                args, _ = self._batch_args([""] * B)
                programs.append((f"step/{B}", self._step,
                                 jax.tree.map(spec, args)))
                if B >= max_batch:
                    break
                B *= 2
            for cap in PATCH_BUCKETS:
                programs.append((f"patch/{cap}", _apply_patches, jax.tree.map(
                    spec, self._patch_args(cap, {}, [], []))))
        seconds = {}
        for name, fn, args in programs:
            t0 = time.perf_counter()
            fn.lower(*tables, *args).compile()
            seconds[name] = time.perf_counter() - t0
        with self._mlock:
            # the shapes compiled, not the current ones: tables that grew
            # meanwhile still read as cold
            self._warm_shapes, self._warm_batch = shapes, B
        return seconds

    def publish_batch_collect(self, pending):
        """Stage 2: fetch + decode a submitted batch's results."""
        if isinstance(pending, tuple) and len(pending) == 2 \
                and pending[0] == "host":
            return pending[1]
        (topics, too_long, fids, fanout, overflow, fan_any, counters,
         (t0, t1)) = pending
        try:
            # ONE device_get for all needed outputs: it issues
            # copy_to_host_async for every array before materializing,
            # so the transfers overlap into ~one device round trip.
            # Serial np.asarray calls cost a full round trip EACH,
            # which dominated the e2e broker latency at a high RTT. The [B, W] fanout block
            # starts its copy speculatively so the fan_any=True case
            # (dense rows matched) costs no SECOND dependent round trip;
            # it only materializes when needed. The kernel counters
            # (when enabled) join the SAME device_get — telemetry costs
            # no extra sync.
            try:
                fanout.copy_to_host_async()
            except AttributeError:     # non-jax array (tests/mocks)
                pass
            t2 = time.monotonic_ns()
            if counters is not None:
                fids, overflow, fan_any, counters = jax.device_get(
                    (fids, overflow, fan_any, counters))
            else:
                fids, overflow, fan_any = jax.device_get(
                    (fids, overflow, fan_any))
            t3 = time.monotonic_ns()
            if fan_any:
                fan = np.asarray(fanout)
            else:
                fan = np.zeros(fanout.shape, np.uint32)
            with self._mlock:
                res = self._decode_locked(topics, too_long, fids, fan,
                                          overflow)
            tel = self.telemetry
            if tel is not None:
                try:   # telemetry must never break the serving path
                    tel.on_batch(
                        counters, n_topics=len(topics),
                        submit_ns=t1 - t0, step_ns=t3 - t2,
                        decode_ns=time.monotonic_ns() - t3,
                        t_submit_ns=t0, t_collect_ns=t3)
                except Exception:  # noqa: BLE001 — observe-plane bug
                    pass
            return res
        finally:
            with self._mlock:
                self.index.end_inflight()

    def _publish_batch_host(self, topics):
        """Serve one batch from the host matcher (cpu-platform path).

        Same ``(matched, aux, slots, fallback)`` contract as the device
        decode.  The host walk is exact and depth-unbounded, so there is
        no overflow/too-long leg: fallback is always empty.  Slots come
        straight from the subscription dict for every matched filter —
        dense-pool promotion is a device-bandwidth optimization with no
        meaning here.
        """
        with self._mlock:
            self.host_match_count += 1
            tel = self.telemetry
            if tel is not None:
                try:
                    tel.on_host_batch(len(topics))
                except Exception:  # noqa: BLE001 — observe-plane bug
                    pass
            filters = self.index.filters
            any_aux = bool(self._aux_refs)
            matched: list[list[str]] = []
            aux: list[list[str]] = []
            slots_out: list[list[int]] = []
            for topic in topics:
                m: list[str] = []
                a: list[str] = []
                sl: set[int] = set()
                for fid in self._host_matcher.match(topic):
                    filt = filters[fid]
                    if filt is None:
                        continue
                    if fid in self._subs:
                        m.append(filt)
                        sl.update(self._subs[fid])
                    if any_aux and fid in self._aux_refs:
                        a.append(filt)
                matched.append(m)
                aux.append(a)
                slots_out.append(sorted(sl))
            return matched, aux, slots_out, []

    def _decode_locked(self, topics, too_long, fids, fan, overflow):
        # -- vectorized batch decode (the r2 host hot-spot): classify the
        # whole [B, M] fid block with two mask gathers, and expand ALL
        # delivering bitmap words with one shift table instead of a
        # per-topic Python popcount loop — decode cost is O(nonzero
        # words + actual matches), not O(B · per-topic python)
        B_out = len(topics)
        F = max(1, len(self.index.filters))
        fb = fids[:B_out]
        valid = fb >= 0
        safe = np.where(valid, fb, 0)
        sub_hit = valid & self._mask_of("_sub_mask", F)[safe]
        any_aux = bool(self._aux_refs)
        if any_aux:
            aux_hit = valid & self._mask_of("_aux_mask", F)[safe]
        filters = self.index.filters
        matched: list[list[str]] = []
        aux: list[list[str]] = []
        slots_out: list[list[int]] = []

        # bitmap words → slot ids, all topics at once
        fan_b = fan[:B_out]
        rb, wb = np.nonzero(fan_b)
        if len(rb):
            vals = fan_b[rb, wb].astype(np.uint32)
            bits = (vals[:, None] >> np.arange(32, dtype=np.uint32)) & 1
            nz_r, nz_bit = np.nonzero(bits)
            rows_flat = rb[nz_r]                      # non-decreasing
            slots_flat = wb[nz_r] * 32 + nz_bit
            bounds = np.searchsorted(rows_flat, np.arange(B_out + 1))
        else:
            slots_flat = np.zeros(0, np.int64)
            bounds = np.zeros(B_out + 1, np.int64)

        for b in range(B_out):
            row = fb[b]
            sub_fids = row[sub_hit[b]]
            # a fid deleted while the batch was in flight decodes to
            # None — that unsubscribe raced the publish; drop the leg
            # (reuse is prevented by the index's in-flight quarantine)
            matched.append([filters[f] for f in sub_fids
                            if filters[f] is not None])
            aux.append([filters[f] for f in row[aux_hit[b]]
                        if filters[f] is not None]
                       if any_aux else [])
            # hybrid decode: dense (high-degree) filters' shard slots
            # come from the device OR (bitmap words above); low-degree
            # filters' slots from the host dict — O(deliveries) total
            out_slots = set(slots_flat[bounds[b]:bounds[b + 1]].tolist())
            for f in sub_fids:
                fi = int(f)
                if fi not in self._dense_row:
                    out_slots.update(self._subs.get(fi, ()))
            slots_out.append(sorted(out_slots))
        fallback = sorted(set(too_long) | set(np.nonzero(overflow)[0].tolist()))
        return matched, aux, slots_out, fallback

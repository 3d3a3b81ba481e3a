"""Batched wildcard-trie match on device — the north-star kernel.

Replaces the reference's per-message trie walk (``emqx_trie:match/1``,
emqx_trie.erl:282-344 — one ETS lookup per topic level, ×2 at '+'/'#'
branches) with one XLA program matching a whole ``[B, L]`` batch of
tokenized topics against the HBM-resident flat trie of
``emqx_tpu.router.index.TrieIndex``.

Algorithm: K-capped frontier walk. The frontier at step *i* holds the trie
nodes whose path matches the first *i* topic words (≤K of them; K bounds
the number of simultaneously-alive wildcard branches, overflow is reported
so the host oracle can take over for that topic). Each scan step does:

1. emit ``hash_fid`` of every frontier node (a ``prefix/#`` filter matches
   any remaining suffix, including the empty one);
2. at end-of-topic, emit ``node_fid`` (filters ending exactly here);
3. advance: exact child via ≤``max_probes`` linear probes of the edge hash
   table + ``+`` child, then pack the ≤2K candidates back into K slots.

Every matching filter id is emitted exactly once per topic (tree-ness of
the trie — see index.py), so the output needs masking but no dedup.

All control flow is static (lax.scan over L+1 steps, unrolled probe loop):
no data-dependent shapes, everything fuses into gathers + elementwise ops —
HBM-bandwidth-bound, which is the right regime for this workload.

Pallas note (evaluated, intentionally not used here): every hot op in this
kernel is a scattered row/element gather from HBM-resident tables indexed
by data-dependent lanes. Pallas-TPU expresses gathers as either per-block
DMAs (grid step per row — B·K·probes steps ≈ 10^6 latency-bound DMAs per
batch) or VMEM-resident tables (the 1M-filter trie is ~25MB+, over VMEM).
XLA's native gather lowering with the optimization-barrier placement below
is the fast path (measured: 0.03ms/batch at 1M filters); the pipeline-level
win instead comes from overlapping dispatch (see bench.py window).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.router.index import HASH_ID, PAD, TrieIndexArrays

# plain Python ints: module-level jnp scalars are concrete device arrays,
# and closure-captured device arrays inside a scan body hit a catastrophic
# slow path on TPU (measured ~400ms vs 0.03ms for the same probe loop)
_MIX_A = 0x9E3779B1
_MIX_B = 0x85EBCA77

# kernel-plane observability (ISSUE 18): the per-batch counters vector's
# field order, declared ONCE here — observe/device_metrics.py carries a
# literal copy the counters-layout lint (tests/test_kernel_counters_lint
# .py) holds in parity, so the in-kernel packer and the host decoder
# cannot drift. Flat layout packs to [C]; the sharded step packs [S, C]
# (one row per trie shard). All int32, computed alongside the match with
# elementwise reductions only — no extra device sync, no data-dependent
# shapes.
KERNEL_COUNTER_FIELDS = (
    "frontier_peak",   # max per-topic frontier occupancy over all steps (≤K)
    "probe_iters",     # total live edge-hash probe-loop iterations
    "cand_pre",        # valid candidate fids before the M compact
    "cand_post",       # candidate fids surviving the M compact
    "compact_peak",    # max per-topic compact-slot occupancy (M utilization)
    "overflow_rows",   # topics whose K frontier spilled (incomplete match)
    "trunc_rows",      # topics truncated by the M compact
)


def pack_counters(**fields) -> jax.Array:
    """Stack the named counter values in KERNEL_COUNTER_FIELDS order.

    Scalars pack to ``[C]``; per-shard ``[S]`` vectors pack to
    ``[S, C]``.  Keyword-only so a caller can never silently permute
    the layout — order lives in one place.
    """
    if set(fields) != set(KERNEL_COUNTER_FIELDS):
        missing = set(KERNEL_COUNTER_FIELDS) - set(fields)
        extra = set(fields) - set(KERNEL_COUNTER_FIELDS)
        raise TypeError(
            f"pack_counters field mismatch: missing={sorted(missing)} "
            f"extra={sorted(extra)}")
    vals = [jnp.asarray(fields[n], jnp.int32)
            for n in KERNEL_COUNTER_FIELDS]
    return jnp.stack(jnp.broadcast_arrays(*vals), axis=-1)


class DeviceTrie(NamedTuple):
    """TrieIndexArrays uploaded to device (a jit-friendly pytree)."""

    ht_parent: jax.Array   # [H] int32, -1 = empty slot
    ht_word: jax.Array     # [H]
    ht_child: jax.Array    # [H]
    plus_child: jax.Array  # [N]
    hash_fid: jax.Array    # [N]
    node_fid: jax.Array    # [N]


def device_trie(arrays: TrieIndexArrays) -> DeviceTrie:
    return DeviceTrie(
        ht_parent=jnp.asarray(arrays.ht_parent),
        ht_word=jnp.asarray(arrays.ht_word),
        ht_child=jnp.asarray(arrays.ht_child),
        plus_child=jnp.asarray(arrays.plus_child),
        hash_fid=jnp.asarray(arrays.hash_fid),
        node_fid=jnp.asarray(arrays.node_fid),
    )


def _g(x: jax.Array) -> jax.Array:
    """Fusion barrier after a table gather.

    XLA-TPU fuses a gather into its elementwise consumers, and the fused
    loop serializes (~500× slowdown measured on v5e: 11ms → 0.02ms for a
    131k-element probe round). The barrier keeps each gather a standalone
    fast-path gather op.
    """
    return jax.lax.optimization_barrier(x)


def _edge_hash(parent: jax.Array, word: jax.Array, mask: int) -> jax.Array:
    """Must stay bit-identical to index.edge_hash (host builder)."""
    h = (
        parent.astype(jnp.uint32) * jnp.uint32(_MIX_A)
        ^ word.astype(jnp.uint32) * jnp.uint32(_MIX_B)
    )
    h ^= h >> jnp.uint32(15)
    h *= jnp.uint32(0x2C1B3C6D)
    h ^= h >> jnp.uint32(12)
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def _edge_step(parent: jax.Array, word: jax.Array, mask: int) -> jax.Array:
    """Double-hashing stride; must stay bit-identical to index.edge_step
    (odd → coprime with the pow2 table)."""
    h = (
        parent.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
        ^ word.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
    )
    h ^= h >> jnp.uint32(13)
    h *= jnp.uint32(0x165667B1)
    h ^= h >> jnp.uint32(16)
    return ((h | jnp.uint32(1)) & jnp.uint32(mask)).astype(jnp.int32)


def _probe_exact(
    trie: DeviceTrie, parent: jax.Array, word: jax.Array, max_probes: int
) -> tuple[jax.Array, jax.Array]:
    """Exact-edge lookup for [B, K] (parent, word) pairs; -1 on miss.

    The probe bound is builder-verified, so the loop unrolls statically.
    Returns ``(child, iters)`` — iters counts live probe rounds per lane
    (the hash-table health signal: mean ≈ 1 on a well-sized table); the
    count is an elementwise add per unrolled round, DCE'd by XLA when
    the counters output goes unused.
    """
    hmask = trie.ht_parent.shape[0] - 1
    # hash the raw parent (-1 included): indices stay in-bounds via the
    # mask, invalid lanes are killed by `done`, and the obvious
    # where-clamp here triggers an XLA-TPU lowering cliff (~5× slower —
    # a select feeding a gather's index chain inside scan de-vectorizes)
    h = _edge_hash(parent, word, hmask)
    step = _edge_step(parent, word, hmask)
    child = jnp.full_like(parent, -1)
    iters = jnp.zeros(parent.shape, jnp.int32)
    done = parent < 0
    for p in range(max_probes):
        iters = iters + (~done).astype(jnp.int32)
        s = (h + p * step) & hmask
        slot_parent = _g(trie.ht_parent[s])
        hit = (slot_parent == parent) & (_g(trie.ht_word[s]) == word) & ~done
        child = jnp.where(hit, _g(trie.ht_child[s]), child)
        done = done | hit | (slot_parent == -1)
    return child, iters


def _pack_frontier(cand: jax.Array, K: int) -> tuple[jax.Array, jax.Array]:
    """Pack valid (≥0) entries of [B, 2K] into [B, K] slots.

    The frontier is a *set* — order is irrelevant — so a descending sort
    (valid node ids ≥ 0 sort ahead of the -1 padding) packs without any
    scatter; TPU scatters serialized this step badly in profiling.

    Returns (packed [B, K], overflowed [B]).
    """
    n_valid = jnp.sum(cand >= 0, axis=1)                   # [B]
    packed = _g(-jnp.sort(-cand, axis=1)[:, :K])
    return packed, n_valid > K


@functools.partial(jax.jit, static_argnames=("K", "max_probes"))
def match_batch(
    trie: DeviceTrie,
    tokens: jax.Array,     # [B, L] int32 word ids (PAD beyond length)
    lengths: jax.Array,    # [B] int32
    sys_flags: jax.Array,  # [B] bool — first level starts with '$'
    *,
    K: int = 32,
    max_probes: int = 8,
) -> tuple[jax.Array, jax.Array, dict]:
    """Match a topic batch against the trie.

    Returns ``(cand_fids [B, (L+1)*2K] int32, overflow [B] bool,
    mstats)``.  ``cand_fids`` holds each matched filter id exactly once,
    -1 elsewhere.  ``overflow[b]`` means topic *b*'s frontier exceeded K
    and the result may be incomplete — route it through the host oracle.
    ``mstats`` is the match half of the kernel counters (scalar int32
    leaves: frontier_peak / probe_iters / cand_pre / overflow_rows —
    see KERNEL_COUNTER_FIELDS); the compact-side fields are the step
    functions' (router_model) to fill.  The reductions are elementwise
    and ride the same program — XLA DCEs them when the caller drops the
    dict.
    """
    B, L = tokens.shape
    tokens_ext = jnp.concatenate(
        [tokens, jnp.full((B, 1), PAD, tokens.dtype)], axis=1
    )

    frontier0 = jnp.full((B, K), -1, jnp.int32).at[:, 0].set(0)  # root
    overflow0 = jnp.zeros((B,), bool)
    peak0 = jnp.zeros((), jnp.int32)
    probes0 = jnp.zeros((), jnp.int32)

    def step(carry, xs):
        frontier, overflow, peak, probes = carry
        i, tok = xs                               # i scalar, tok [B]
        valid = frontier >= 0
        peak = jnp.maximum(
            peak, jnp.max(jnp.sum(valid.astype(jnp.int32), axis=1)))
        node = jnp.where(valid, frontier, 0)
        active = (i <= lengths)[:, None]          # may still emit '#'
        ended = (i == lengths)[:, None]
        advancing = (i < lengths)[:, None]
        sys_block = (sys_flags & (i == 0))[:, None]

        hash_em = jnp.where(
            valid & active & ~sys_block, _g(trie.hash_fid[node]), -1
        )
        end_em = jnp.where(valid & ended, _g(trie.node_fid[node]), -1)

        wordk = jnp.broadcast_to(tok[:, None], (B, K))
        exact, iters = _probe_exact(
            trie, jnp.where(advancing, frontier, -1), wordk, max_probes
        )
        probes = probes + jnp.sum(iters)
        plus = jnp.where(
            valid & advancing & ~sys_block, _g(trie.plus_child[node]), -1
        )
        nxt, over = _pack_frontier(
            jnp.concatenate([exact, plus], axis=1), K
        )
        return (nxt, overflow | over, peak, probes), (hash_em, end_em)

    (_, overflow, peak, probes), (hash_ems, end_ems) = jax.lax.scan(
        step,
        (frontier0, overflow0, peak0, probes0),
        (jnp.arange(L + 1), tokens_ext.T),
    )
    # [L+1, B, K] → [B, (L+1)*K] each → concat
    cand = jnp.concatenate(
        [
            jnp.moveaxis(hash_ems, 0, 1).reshape(B, -1),
            jnp.moveaxis(end_ems, 0, 1).reshape(B, -1),
        ],
        axis=1,
    )
    mstats = {
        "frontier_peak": peak,
        "probe_iters": probes,
        "cand_pre": jnp.sum((cand >= 0).astype(jnp.int32)),
        "overflow_rows": jnp.sum(overflow.astype(jnp.int32)),
    }
    return cand, overflow, mstats


@functools.partial(jax.jit, static_argnames=("K", "max_probes"))
def match_counts(
    trie: DeviceTrie,
    tokens: jax.Array,
    lengths: jax.Array,
    sys_flags: jax.Array,
    *,
    K: int = 32,
    max_probes: int = 8,
) -> tuple[jax.Array, jax.Array]:
    """Matched-filter count per topic (the emqx_broker_bench LookupRps
    analogue — the full match with only the reduction materialized)."""
    cand, overflow, _ = match_batch(
        trie, tokens, lengths, sys_flags, K=K, max_probes=max_probes
    )
    return jnp.sum(cand >= 0, axis=1), overflow


@functools.partial(jax.jit, static_argnames=("M",))
def compact_fids(cand: jax.Array, *, M: int = 128) -> tuple[jax.Array, jax.Array]:
    """Compact sparse candidates [B, S] to the first M matches [B, M].

    Returns (fids [B, M] padded with -1, truncated [B]). Stable order.
    """
    order = _g(jnp.argsort(cand < 0, axis=1, stable=True))
    packed = _g(jnp.take_along_axis(cand, order[:, :M], axis=1))
    n = jnp.sum(cand >= 0, axis=1)
    return packed, n > M


# ---------------------------------------------------------------------------
# sharded trie: S per-shard tries stacked into [S, ...] buffers
# ---------------------------------------------------------------------------


def stacked_device_trie(shard_arrays) -> DeviceTrie:
    """Stack S per-shard TrieIndexArrays into one [S, ...] DeviceTrie.

    The edge hash tables must already share one pow2 size H — the probe
    mask (H-1) is baked per stacked buffer, so ShardedTrieIndex.ensure()
    equalizes them before this runs.  Node arrays just pad to the max N
    with -1: a -1 child/fid lane is already "miss" everywhere in the
    kernel, so padding is semantically invisible.

    Returns host (numpy-backed) arrays — the caller device_puts the
    pytree with the ``trie_sub`` sharding (shard axis 0 over ``tp``).
    """
    sizes = {a.ht_parent.shape[0] for a in shard_arrays}
    if len(sizes) != 1:
        raise ValueError(f"unequal edge-table sizes across shards: {sizes}")
    N = max(a.plus_child.shape[0] for a in shard_arrays)

    def pad_n(x: np.ndarray) -> np.ndarray:
        if x.shape[0] == N:
            return x
        return np.concatenate(
            [x, np.full(N - x.shape[0], -1, x.dtype)])

    return DeviceTrie(
        ht_parent=np.stack([a.ht_parent for a in shard_arrays]),
        ht_word=np.stack([a.ht_word for a in shard_arrays]),
        ht_child=np.stack([a.ht_child for a in shard_arrays]),
        plus_child=np.stack([pad_n(a.plus_child) for a in shard_arrays]),
        hash_fid=np.stack([pad_n(a.hash_fid) for a in shard_arrays]),
        node_fid=np.stack([pad_n(a.node_fid) for a in shard_arrays]),
    )


@functools.partial(jax.jit, static_argnames=("K", "max_probes"))
def match_batch_sharded(
    trie: DeviceTrie,      # fields [S, H] / [S, N]
    tokens: jax.Array,     # [B, L]
    lengths: jax.Array,    # [B]
    sys_flags: jax.Array,  # [B]
    *,
    K: int = 32,
    max_probes: int = 8,
) -> tuple[jax.Array, jax.Array, dict]:
    """match_batch vmapped over the shard axis of a stacked trie.

    Each shard walks the SAME (tp-replicated) topic batch against its
    own subscription slice, so the returned fids are shard-LOCAL.
    Overflow is per-shard: shard s's K-frontier can spill on a topic
    even when the replicated trie's would not (its wildcard branches
    are a subset but the cap is per walk) and vice versa — the [S, B]
    flags are OR-reduced because any spilled shard makes the merged
    result potentially incomplete for that topic.

    Returns ``(cand [S, B, (L+1)*2K], overflow [B], mstats)``; the
    vmap turns every mstats leaf into a PER-SHARD [S] vector — the
    shard-skew signal the host fold wants — including overflow_rows,
    which stays per-shard (pre-OR) by design.
    """
    cand, over, mstats = jax.vmap(
        lambda t: match_batch(
            t, tokens, lengths, sys_flags, K=K, max_probes=max_probes
        )
    )(trie)
    return cand, jnp.any(over, axis=0), mstats


@functools.partial(jax.jit, static_argnames=("M", "n_shards"))
def compact_fids_sharded(
    cand: jax.Array, *, M: int = 128, n_shards: int = 1
) -> tuple[jax.Array, jax.Array]:
    """Per-shard compact + local→global fid translation + merge.

    ``cand`` is the [S, B, C] shard-local candidate tensor from
    ``match_batch_sharded``.  Each shard compacts its own candidates to
    M slots (so the merge tensor is [B, S·M], tiny next to C), local
    fids translate to the interleaved global namespace
    (``global = local * S + shard``), and a second stable compact packs
    the shard-major concatenation down to the first M global matches.

    Returns (fids [B, M] global, truncated [B]).  Truncation is the OR
    of any per-shard spill and the merged spill — either loses matches.
    For S=1 the translation is the identity and the second compact of
    an already-packed row is a no-op, so this degenerates bit-for-bit
    to ``compact_fids``.
    """
    S, B, _ = cand.shape
    per, trunc = jax.vmap(lambda c: compact_fids(c, M=M))(cand)
    shard_ids = jnp.arange(S, dtype=per.dtype)[:, None, None]
    per = jnp.where(per >= 0, per * n_shards + shard_ids, -1)
    merged = jnp.moveaxis(per, 0, 1).reshape(B, S * M)   # [B, S*M]
    fids, trunc2 = compact_fids(merged, M=M)
    return fids, jnp.any(trunc, axis=0) | trunc2

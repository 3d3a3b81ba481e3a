"""Device trie matcher ↔ host oracle equivalence (the round-1 "aha" slice:
same results as the emqx_trie-semantics oracle on randomized filter sets)."""

import random

import numpy as np
import pytest

from emqx_tpu.core import topic as T
from emqx_tpu.router.index import TrieIndex
from emqx_tpu.router.trie import Trie
from emqx_tpu.ops import trie_match as tm


def build(filters, max_levels=10):
    idx = TrieIndex(max_levels=max_levels)
    idx.load(filters)
    arrays = idx.ensure()
    return idx, tm.device_trie(arrays)


def run_match(idx, trie_dev, topics, K=32):
    tokens, lengths, sys_flags, too_long = idx.tokenize(topics)
    assert not too_long
    cand, overflow, _ = tm.match_batch(
        trie_dev, np.asarray(tokens), np.asarray(lengths), np.asarray(sys_flags), K=K
    )
    cand = np.asarray(cand)
    out = []
    for b in range(len(topics)):
        fids = cand[b][cand[b] >= 0]
        assert len(set(fids.tolist())) == len(fids), "duplicate emission"
        out.append(sorted(idx.filters[f] for f in fids))
    return out, np.asarray(overflow)


def test_basic_match():
    filters = ["a/+/c", "a/#", "+/b/c", "#", "a/b/+", "a/b/c", "x"]
    idx, dev = build(filters)
    got, overflow = run_match(idx, dev, ["a/b/c", "a", "x", "q/r", "$SYS/x"])
    assert not overflow.any()
    assert got[0] == sorted(["a/+/c", "a/#", "+/b/c", "#", "a/b/+", "a/b/c"])
    assert got[1] == sorted(["a/#", "#"])
    assert got[2] == sorted(["#", "x"])
    assert got[3] == sorted(["#"])
    assert got[4] == []


def test_hash_matches_parent_and_empty_levels():
    filters = ["sport/#", "sport/+", "+/+", "a//c", "a/+/c"]
    idx, dev = build(filters)
    got, _ = run_match(idx, dev, ["sport", "sport/", "a//c", "sport/tennis/x"])
    assert got[0] == ["sport/#"]
    assert got[1] == sorted(["sport/#", "sport/+", "+/+"])
    assert got[2] == sorted(["a//c", "a/+/c"])
    assert got[3] == ["sport/#"]


def test_unknown_words_match_only_wildcards():
    idx, dev = build(["+/x", "#", "known/x"])
    got, _ = run_match(idx, dev, ["zzz/x", "zzz/zzz"])
    assert got[0] == sorted(["+/x", "#"])
    assert got[1] == ["#"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_randomized_equivalence_vs_oracle(seed):
    rng = random.Random(seed)
    alphabet = ["a", "b", "c", "d", "e", ""]
    oracle = Trie()
    filters = set()
    for _ in range(600):
        ws = [rng.choice(alphabet + ["+", "#"]) for _ in range(rng.randint(1, 7))]
        if "#" in ws:
            ws = ws[: ws.index("#") + 1]
        f = T.join(ws)
        if T.validate_filter(f) and f not in filters:
            filters.add(f)
            oracle.insert(f)
    # exact-topic filters too (no wildcard)
    for _ in range(100):
        f = T.join(rng.choice(alphabet[:5]) for _ in range(rng.randint(1, 7)))
        if f not in filters:
            filters.add(f)
            oracle.insert(f)

    idx, dev = build(sorted(filters))
    topics = []
    for _ in range(256):
        nw = [rng.choice(alphabet[:5] + ["$x", "zz"]) for _ in range(rng.randint(1, 8))]
        topics.append(T.join(nw))

    got, overflow = run_match(idx, dev, topics, K=64)
    for b, topic in enumerate(topics):
        expect = sorted(oracle.match(topic))
        if overflow[b]:
            continue  # kernel reported incompleteness → host fallback
        assert got[b] == expect, (topic, got[b], expect)
    assert overflow.sum() < len(topics) // 4


def test_frontier_overflow_reported_not_wrong():
    """With tiny K the kernel must flag overflow rather than silently drop."""
    # '+' and exact branch points along one path grow the frontier
    filters = ["+/" * d + "#" for d in range(0, 7)] + ["a/" * d + "#" for d in range(0, 7)]
    filters = sorted(set(f for f in filters if T.validate_filter(f)))
    idx, dev = build(filters)
    oracle = Trie()
    for f in filters:
        oracle.insert(f)
    topics = ["a/a/a/a/a/a"]
    got, overflow = run_match(idx, dev, topics, K=2)
    if not overflow[0]:
        assert got[0] == sorted(oracle.match(topics[0]))


def test_deleted_filters_dont_match():
    idx = TrieIndex(max_levels=6)
    idx.load(["a/+", "a/#", "b/+"])
    idx.delete("a/#")
    dev = tm.device_trie(idx.ensure())
    got, _ = run_match(idx, dev, ["a/x"])
    assert got[0] == ["a/+"]
    # fid slot reuse: new filter takes the freed id
    fid = idx.insert("c/+")
    assert idx.filters[fid] == "c/+"
    dev = tm.device_trie(idx.ensure())
    got, _ = run_match(idx, dev, ["c/z", "a/x"])
    assert got[0] == ["c/+"]
    assert got[1] == ["a/+"]


def test_compact_fids():
    import jax.numpy as jnp

    cand = jnp.array([[-1, 5, -1, 3, -1], [7, -1, -1, -1, -1], [-1] * 5])
    packed, truncated = tm.compact_fids(cand, M=2)
    assert packed.tolist() == [[5, 3], [7, -1], [-1, -1]]
    assert truncated.tolist() == [False, False, False]


def test_match_counts():
    idx, dev = build(["a/+", "a/#", "#"])
    tokens, lengths, sys_flags, _ = idx.tokenize(["a/x", "q", "$S/x"])
    counts, overflow = tm.match_counts(
        dev, np.asarray(tokens), np.asarray(lengths), np.asarray(sys_flags)
    )
    assert counts.tolist() == [3, 1, 0]


# -- the level loop stops at the batch's deepest topic ----------------------

DEEP = "/".join("abcdefghijklmnop")            # 16 levels, the tokenizer's max
WALK_FILTERS = ["#", "+", "+/x", "a/#", "a/+", "a/b", "a/b/c", "$SYS/#",
                "+/+/+", "a/+/c/#", DEEP, "a/b/c/d/e/f/g/h/i/j/k/l/m/n/o/+",
                "a/b/c/d/e/f/g/#", "/#", "x"]
# base batches whose deepest topic has 0, 1, 2 and 16 levels; "" is one
# empty level, and only padding rows have none
WALK_BATCHES = {
    0: [],
    1: ["a", "x", "$SYS", "", "q"],
    2: ["a/b", "q/x", "$SYS/x", "a", "", "/"],
    16: ["a/b", DEEP, "a/b/c/d/e/f/g/h/i/j/k/l/m/n/o/z", "$SYS/a/b", ""],
}


def _walk(idx, dev, topics, pad=3):
    """match_batch on ``topics`` plus ``pad`` padding rows (length 0,
    sys flag set, as the model pads a batch to its bucket)."""
    tok, lens, sysf, too_long = idx.tokenize(list(topics) + [""] * pad)
    assert not too_long
    lens[len(topics):] = 0
    sysf[len(topics):] = True
    cand, overflow, mstats = tm.match_batch(dev, tok, lens, sysf)
    return (np.asarray(cand), np.asarray(overflow),
            {k: int(v) for k, v in mstats.items()})


@pytest.mark.parametrize("depth", sorted(WALK_BATCHES))
def test_rows_identical_with_and_without_deep_row(depth):
    """A row's candidates, in their positions, and its overflow flag do
    not depend on how deep the other rows of its batch go."""
    idx, dev = build(WALK_FILTERS, max_levels=16)
    base = WALK_BATCHES[depth]
    cand, over, st = _walk(idx, dev, base)
    cand_d, over_d, st_d = _walk(idx, dev, [DEEP] + base)
    cand_d, over_d = cand_d[1:], over_d[1:]
    assert cand.shape == cand_d.shape == (len(base) + 3, 17 * 2 * 32)
    np.testing.assert_array_equal(cand, cand_d)
    np.testing.assert_array_equal(over, over_d)
    assert st["walk_levels"] == depth + 1
    assert st_d["walk_levels"] == 17


def test_mixed_length_batches_vs_oracle():
    oracle = Trie()
    for f in WALK_FILTERS:
        oracle.insert(f)
    idx, dev = build(WALK_FILTERS, max_levels=16)
    rng = random.Random(26)
    words = ["a", "b", "c", "x", "", "$SYS"]
    for trial in range(6):
        topics = ["", "$SYS/x", "a/x", DEEP, "a/b/c/d/e/f/g/h"]
        for _ in range(20):
            n = rng.choice([0, 1, 2, 3, 7, 16][: 2 + trial % 5])
            topics.append(T.join(rng.choice(words) for _ in range(n))
                          if n else "")
        rng.shuffle(topics)
        cand, overflow, _ = _walk(idx, dev, topics, pad=8)
        assert not overflow.any()
        for b, topic in enumerate(topics):
            fids = cand[b][cand[b] >= 0]
            assert len(set(fids.tolist())) == len(fids), "duplicate emission"
            got = sorted(idx.filters[f] for f in fids)
            assert got == sorted(oracle.match(topic)), (topic, got)
        # padding rows match nothing, not even the root '#'
        assert (cand[len(topics):] < 0).all()


@pytest.mark.parametrize("depth", sorted(WALK_BATCHES))
def test_match_counters_add_up_with_a_deep_row(depth):
    """The deep row's extra levels cost the other rows nothing: the
    summed counters of the joint batch are the two parts' sums, its
    frontier peak the larger of theirs."""
    idx, dev = build(WALK_FILTERS, max_levels=16)
    base = WALK_BATCHES[depth]
    _, _, st = _walk(idx, dev, base)
    _, _, st_deep = _walk(idx, dev, [DEEP], pad=0)
    _, _, st_both = _walk(idx, dev, base + [DEEP])
    for k in ("probe_iters", "cand_pre", "overflow_rows"):
        assert st_both[k] == st[k] + st_deep[k], k
    assert st_both["frontier_peak"] == max(st["frontier_peak"],
                                           st_deep["frontier_peak"])


@pytest.mark.parametrize("shards", [None, 2])
@pytest.mark.parametrize("depth", sorted(WALK_BATCHES))
def test_step_counts_walk_levels(depth, shards):
    """The router step's walk_levels counter reads max(lengths) + 1 per
    launch; the S=2 sharded step reads it in every shard."""
    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.observe.device_metrics import KernelCounters
    from emqx_tpu.router.index import ShardedTrieIndex

    index = (ShardedTrieIndex(shards, max_levels=16) if shards
             else TrieIndex(max_levels=16))
    model = RouterModel(index, n_sub_slots=64, kernel_telemetry=True)
    for i, f in enumerate(WALK_FILTERS):
        model.subscribe(f, i)
    # a topic past max_levels takes a length-0 row, like padding
    topics = WALK_BATCHES[depth] or ["/".join("abcdefghijklmnopq")]
    pending = model.publish_batch_submit(topics)
    kc = KernelCounters(np.asarray(pending[6]))
    model.publish_batch_collect(pending)
    assert kc.n_shards == (shards or 1)
    assert kc.field("walk_levels").tolist() == [depth + 1] * kc.n_shards

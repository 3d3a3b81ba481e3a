"""RouterModel end-to-end: match + fan-out, single-device and on the mesh."""

import numpy as np
import pytest

from emqx_tpu.models.router_model import RouterModel
from emqx_tpu.router.index import TrieIndex
from emqx_tpu.router.trie import Trie


def make_model(mesh=None, n_sub_slots=256):
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=n_sub_slots, K=16, M=32, mesh=mesh)
    m.subscribe("a/+/c", 3)
    m.subscribe("a/#", 3)
    m.subscribe("a/#", 7)
    m.subscribe("x/y", 100)
    m.subscribe("#", 200)
    return m


def test_publish_batch_single_device():
    m = make_model()
    matched, aux, slots, fallback = m.publish_batch(["a/b/c", "x/y", "nope", "$SYS/x"])
    assert fallback == []
    assert sorted(matched[0]) == ["#", "a/#", "a/+/c"]
    assert slots[0] == [3, 7, 200]
    assert sorted(matched[1]) == ["#", "x/y"]
    assert slots[1] == [100, 200]
    assert matched[2] == ["#"] and slots[2] == [200]
    assert matched[3] == [] and slots[3] == []


def test_unsubscribe_updates_fanout():
    m = make_model()
    m.unsubscribe("a/#", 3)
    matched, _aux, slots, _ = m.publish_batch(["a/q"])
    assert sorted(matched[0]) == ["#", "a/#"]
    assert slots[0] == [7, 200]
    m.unsubscribe("a/#", 7)   # last subscriber → filter drops out
    matched, _aux, slots, _ = m.publish_batch(["a/q"])
    assert sorted(matched[0]) == ["#"]


def test_batch_padding_no_phantom_matches():
    m = make_model()
    # 3 topics pad to a 64-bucket; padding rows must match nothing
    matched, _aux, slots, _ = m.publish_batch(["q", "q", "q"])
    assert all(mm == ["#"] for mm in matched)
    assert len(matched) == 3


def test_mesh_sharded_equals_single(rng):
    import jax
    from emqx_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8
    mesh = make_mesh(8, shape=(4, 2))
    # W=16 words → shards 8 per device over tp=2
    m1 = make_model(mesh=None, n_sub_slots=512)
    m2 = make_model(mesh=mesh, n_sub_slots=512)
    topics = ["a/b/c", "x/y", "a/zz", "$SYS/x"] * 16
    r1 = m1.publish_batch(topics)
    r2 = m2.publish_batch(topics)
    assert r1[0] == r2[0]
    assert r1[1] == r2[1]
    assert r1[2] == r2[2]


def test_randomized_model_vs_oracle(rng):
    oracle = Trie()
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=1024, K=32, M=64)
    subs: dict[str, set[int]] = {}
    words = ["a", "b", "c"]
    for i in range(300):
        ws = [rng.choice(words + ["+"]) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.3:
            ws.append("#")
        f = "/".join(ws)
        slot = rng.randrange(1024)
        m.subscribe(f, slot)
        if f not in subs:
            subs[f] = set()
            oracle.insert(f)
        subs[f].add(slot)
    topics = ["/".join(rng.choice(words) for _ in range(rng.randint(1, 6))) for _ in range(128)]
    matched, aux, slots, fallback = m.publish_batch(topics)
    for b, t in enumerate(topics):
        if b in fallback:
            continue
        assert sorted(matched[b]) == sorted(oracle.match(t)), t
        expect_slots = sorted(set().union(*[subs[f] for f in matched[b]]) if matched[b] else set())
        assert slots[b] == expect_slots, t


def test_incremental_deltas_vs_oracle(rng):
    """Randomized subscribe/unsubscribe delta sequences applied AFTER the
    first device build must route identically to the host oracle WITHOUT
    any full rebuild — the emqx_trie.erl:113-144 incremental-maintenance
    contract (VERDICT round-1 item 2)."""
    oracle = Trie()
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=1024, K=32, M=64)
    subs: dict[str, set[int]] = {}
    words = ["a", "b", "c", "d"]

    def rand_filter():
        ws = [rng.choice(words + ["+"]) for _ in range(rng.randint(1, 5))]
        if rng.random() < 0.25:
            ws.append("#")
        return "/".join(ws)

    # seed set → first full build
    for _ in range(100):
        f, slot = rand_filter(), rng.randrange(1024)
        m.subscribe(f, slot)
        if f not in subs:
            subs[f] = set()
            oracle.insert(f)
        subs[f].add(slot)
    m.publish_batch(["a"])              # forces initial build
    base_uploads = m.upload_count
    assert base_uploads >= 1

    topics = ["/".join(rng.choice(words) for _ in range(rng.randint(1, 6)))
              for _ in range(64)]
    for _round in range(8):
        # a chunk of random deltas: inserts + deletes
        for _ in range(20):
            if subs and rng.random() < 0.45:
                f = rng.choice(sorted(subs))
                slot = rng.choice(sorted(subs[f]))
                m.unsubscribe(f, slot)
                subs[f].discard(slot)
                if not subs[f]:
                    del subs[f]
                    oracle.delete(f)
            else:
                f, slot = rand_filter(), rng.randrange(1024)
                m.subscribe(f, slot)
                if f not in subs:
                    subs[f] = set()
                    oracle.insert(f)
                subs[f].add(slot)
        matched, aux, slots, fallback = m.publish_batch(topics)
        for b, t in enumerate(topics):
            if b in fallback:
                continue
            assert sorted(matched[b]) == sorted(oracle.match(t)), t
            expect = sorted(set().union(
                *[subs[f] for f in matched[b]]) if matched[b] else set())
            assert slots[b] == expect, t
    # the whole churn went through incremental scatters, not rebuilds
    assert m.upload_count == base_uploads
    assert m.patch_count >= 8


def test_incremental_growth_triggers_rebuild():
    """Node-capacity exhaustion flips needs_rebuild and the next publish
    does one clean double-buffered upload."""
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=16, M=32)
    m.subscribe("seed/x", 1)
    m.publish_batch(["seed/x"])
    uploads0 = m.upload_count
    # pile on distinct filters until the headroom runs out
    for i in range(3000):
        m.subscribe(f"grow/{i}/leaf", i % 64)
    matched, _aux, _, _ = m.publish_batch(["grow/2999/leaf"])
    assert matched[0] == ["grow/2999/leaf"]
    assert m.upload_count > uploads0            # grew via full rebuild
    matched, _aux, _, _ = m.publish_batch(["seed/x"])
    assert matched[0] == ["seed/x"]


def test_incremental_filter_reinsert_after_delete(rng):
    """Delete then re-insert of the same filter (fid reuse) must route
    correctly through the incremental path."""
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=16, M=32)
    m.subscribe("a/b", 1)
    m.subscribe("c/d", 2)
    m.publish_batch(["a/b"])
    m.unsubscribe("a/b", 1)             # filter drops out, fid freed
    matched, _aux, _, _ = m.publish_batch(["a/b"])
    assert matched[0] == []
    m.subscribe("e/f", 3)               # likely reuses the freed fid
    m.subscribe("a/b", 4)
    matched, _aux, slots, _ = m.publish_batch(["a/b", "e/f", "c/d"])
    assert matched[0] == ["a/b"] and slots[0] == [4]
    assert matched[1] == ["e/f"] and slots[1] == [3]
    assert matched[2] == ["c/d"] and slots[2] == [2]


def test_dense_pool_promotion_and_demotion(rng):
    """A filter crossing dense_threshold moves into the device pool and
    back out; routing stays exact through both transitions (the
    emqx_broker_helper >1024-subscriber shard-split analogue)."""
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=512, K=16, M=32,
                    dense_threshold=16)
    for s in range(40):                      # degree 40 > threshold 16
        m.subscribe("hot/topic", s)
    m.subscribe("cold/topic", 7)
    matched, _aux, slots, _ = m.publish_batch(["hot/topic", "cold/topic"])
    fid = m.index.fid_of("hot/topic")
    assert fid in m._dense_row               # promoted
    assert matched[0] == ["hot/topic"] and slots[0] == list(range(40))
    assert matched[1] == ["cold/topic"] and slots[1] == [7]
    # drain below threshold//2 → demotion
    for s in range(36):
        m.unsubscribe("hot/topic", s)
    assert fid not in m._dense_row           # demoted
    matched, _aux, slots, _ = m.publish_batch(["hot/topic"])
    assert slots[0] == [36, 37, 38, 39]
    # pool row was freed and zeroed: a new hot filter reusing it must
    # not inherit stale bits
    for s in range(100, 120):
        m.subscribe("hot2/t", s)
    matched, _aux, slots, _ = m.publish_batch(["hot2/t"])
    assert slots[0] == list(range(100, 120))


def test_hybrid_randomized_vs_oracle(rng):
    """Randomized churn crossing the dense threshold in both directions
    must stay equivalent to the host oracle."""
    oracle = Trie()
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=256, K=32, M=64,
                    dense_threshold=8)
    subs: dict[str, dict[int, int]] = {}
    words = ["a", "b", "c"]

    def rand_filter():
        ws = [rng.choice(words + ["+"]) for _ in range(rng.randint(1, 4))]
        if rng.random() < 0.3:
            ws.append("#")
        return "/".join(ws)

    for _round in range(6):
        for _ in range(60):
            if subs and rng.random() < 0.4:
                f = rng.choice(sorted(subs))
                slot = rng.choice(sorted(subs[f]))
                m.unsubscribe(f, slot)
                subs[f][slot] -= 1
                if subs[f][slot] == 0:
                    del subs[f][slot]
                if not subs[f]:
                    del subs[f]
                    oracle.delete(f)
            else:
                f, slot = rand_filter(), rng.randrange(256)
                m.subscribe(f, slot)
                if f not in subs:
                    subs[f] = {}
                    oracle.insert(f)
                subs[f][slot] = subs[f].get(slot, 0) + 1
        topics = ["/".join(rng.choice(words)
                           for _ in range(rng.randint(1, 5)))
                  for _ in range(64)]
        matched, aux, slots, fallback = m.publish_batch(topics)
        for b, t in enumerate(topics):
            if b in fallback:
                continue
            assert sorted(matched[b]) == sorted(oracle.match(t)), t
            expect = sorted(set().union(
                *[subs[f].keys() for f in matched[b]])
                if matched[b] else set())
            assert slots[b] == expect, t


def test_fixed_slot_space_at_scale():
    """Many more subscribers than slots: the shard space stays fixed and
    device structures don't grow with subscriber count (BASELINE
    config 3's 10M-sub regime in miniature)."""
    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.core.message import Message

    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=16,
                        M=32, dense_threshold=16)
    b = Broker(router_model=model)
    n = 500                                # >> 64 slots
    for i in range(n):
        b.subscribe(f"c{i}", "bcast/all")
        b.subscribe(f"c{i}", f"own/c{i}")
    assert b.slots.capacity == 64
    # pool holds exactly the one hot filter; inline rows cover the rest
    assert len(model._dense_row) == 1
    deliveries = b.publish_batch(
        [Message(topic="bcast/all", payload=b"x"),
         Message(topic="own/c123", payload=b"y")])
    assert len(deliveries[0]) == n          # every client got the bcast
    assert set(deliveries[1]) == {"c123"}   # sharded slot decode exact


def test_inflight_fid_quarantine_prevents_wrong_delivery():
    """submit/collect split: a fid freed while a batch is in flight must
    not be REUSED before the batch decodes — reuse would decode the old
    topic's match as the new filter (wrong-subscriber delivery)."""
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=16,
                        M=32)
    model.subscribe("old/topic", 3)
    model.refresh()
    pending = model.publish_batch_submit(["old/topic"])
    # while in flight: the old filter goes away and a new one arrives
    model.unsubscribe("old/topic", 3)
    old_fid = None
    new_fid = model.subscribe("new/topic", 5)
    matched, _aux, slots, fallback = model.publish_batch_collect(pending)
    # the raced unsubscribe drops the leg; it must NOT become new/topic
    assert matched[0] in ([], ["old/topic"])
    assert "new/topic" not in matched[0]
    # the freed fid is only reusable AFTER collect
    assert model.index._inflight == 0
    f2 = model.publish_batch(["new/topic"])
    assert f2[0][0] == ["new/topic"] and f2[2][0] == [5]


class _CompileCounter:
    """Counts XLA backend compiles while active."""

    def __enter__(self):
        import jax

        self.n = 0

        def listener(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.n += 1
        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)


def test_warm_compiles_every_serving_program():
    """After warm(max_batch) no batch bucket up to max_batch and no
    patch bucket compiles again — the lane's frames never wait on XLA."""
    from emqx_tpu.models import router_model as rm

    m = make_model()
    seconds = m.warm(1024)
    assert set(seconds) == (
        {f"step/{b}" for b in (64, 128, 256, 512, 1024)}
        | {f"patch/{c}" for c in rm.PATCH_BUCKETS})
    assert m.warm(1024) == {}          # already warm: compiles nothing
    with _CompileCounter() as cc:
        for n in (1, 64, 65, 300, 1000):
            m.publish_batch_submit(["a/b/c"] * n, compiled_only=True)
        m.subscribe("a/new/leaf", 9)   # one incremental patch
        matched, _, _, _ = m.publish_batch(["a/new/leaf"])
    assert "a/new/leaf" in matched[0]
    assert m.patch_count >= 1
    assert cc.n == 0


def test_warm_to_the_top_bucket_compiles_only_the_serving_set():
    """The walk's level bound is a device value: warming to the lane's
    top bucket compiles one step program per batch bucket and the
    patch buckets, nothing per topic depth."""
    from emqx_tpu.models import router_model as rm

    m = make_model()
    seconds = m.warm(16384)
    assert set(seconds) == (
        {f"step/{64 << k}" for k in range(9)}
        | {f"patch/{c}" for c in rm.PATCH_BUCKETS})
    with _CompileCounter() as cc:
        for topic in ("a", "a/b/c", "a/b/c/d/e/f/g/h"):
            m.publish_batch_submit([topic] * 3, compiled_only=True)
    assert cc.n == 0


def test_compiled_only_submit_refuses_cold_programs():
    """A batch past the warmed buckets, or tables that grew since the
    warm, raise ColdTables before any launch; warm() clears it."""
    from emqx_tpu.models.router_model import ColdTables

    m = make_model()
    m.warm(128)
    launches = m.launch_count
    with pytest.raises(ColdTables):
        m.publish_batch_submit(["x/y"] * 129, compiled_only=True)
    for i in range(3000):                # outgrow the node headroom
        m.subscribe(f"grow/{i}/leaf", i % 256)
    with pytest.raises(ColdTables):
        m.publish_batch_submit(["grow/7/leaf"], compiled_only=True)
    assert m.launch_count == launches
    assert m.warm(128)                   # new shapes compile
    matched, _, _, _ = m.publish_batch_collect(
        m.publish_batch_submit(["grow/7/leaf"], compiled_only=True))
    assert sorted(matched[0]) == ["#", "grow/7/leaf"]


def test_pool_growth_with_pending_trie_updates_compiles_nothing():
    """The rowmap outgrows its capacity while the trie keeps its shapes
    and holds pending updates: a compiled_only submit re-uploads instead
    of scattering at the new shapes, and refuses before any compile."""
    from emqx_tpu.models.router_model import ColdTables

    m = make_model()
    for i in range(40):
        m.subscribe(f"d/{i}/x/y/z/w", i)
    m.warm(128)
    trie_shapes = m._table_shapes()[:-2]
    rowmap_cap = m._rowmap_host.shape[0]
    # filters on nodes that already exist: no trie growth, only pending
    # node updates, and more fids than the rowmap holds
    for i in range(40):
        for f in ("d/{}", "d/{}/x", "d/{}/x/y", "d/{}/x/y/z"):
            m.subscribe(f.format(i), i)
    assert len(m.index.filters) > rowmap_cap
    assert not m.index.needs_rebuild and m.index.pending
    with _CompileCounter() as cc:
        with pytest.raises(ColdTables):
            m.publish_batch_submit(["d/3/x"], compiled_only=True)
    assert cc.n == 0
    assert m._table_shapes()[:-2] == trie_shapes
    assert m._rowmap_host.shape[0] > rowmap_cap
    assert m.warm(128)
    matched, _, _, _ = m.publish_batch_collect(
        m.publish_batch_submit(["d/3/x"], compiled_only=True))
    assert sorted(matched[0]) == ["#", "d/3/x"]


def test_warm_leaves_the_model_lock_free_while_compiling():
    """warm() compiles outside the model lock: a subscribe from another
    thread lands while the compiles run."""
    import threading

    m = make_model()
    m.publish_batch(["a/b/c"])
    done = threading.Event()
    compiling = threading.Event()
    real_lower = m._step.lower

    class _Step:
        def lower(self, *args):
            compiling.set()
            assert done.wait(30)   # the subscribe below got the lock
            return real_lower(*args)

    step, m._step = m._step, _Step()
    t = threading.Thread(target=m.warm, args=(64,))
    t.start()
    assert compiling.wait(30)
    m.subscribe("late/x", 1)
    done.set()
    t.join(60)
    m._step = step
    assert m._warm_batch == 64
    assert m.publish_batch(["late/x"])[0][0] == ["#", "late/x"]


def test_patch_past_top_bucket_reuploads_same_shapes():
    """A drain larger than the top patch rung re-uploads the tables at
    their current shapes instead of compiling a new scatter."""
    from emqx_tpu.models import router_model as rm

    top = rm.PATCH_BUCKETS[-1]
    m = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=16, M=32)
    for i in range(top + 100):
        m.subscribe(f"seed/{i}/x", i % 64)
    m.publish_batch(["seed/1/x"])
    shapes, uploads = m._table_shapes(), m.upload_count
    for i in range(top + 50):          # one node_fid write per delete
        m.unsubscribe(f"seed/{i}/x", i % 64)
    assert max(map(len, m.index.pending.values())) > top
    assert not m.index.needs_rebuild
    live = f"seed/{top + 60}/x"
    matched, _, _, _ = m.publish_batch([live, "seed/0/x"])
    assert matched == [[live], []]
    assert m.upload_count == uploads + 1
    assert m._table_shapes() == shapes

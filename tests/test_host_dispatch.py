"""Platform-aware kernel dispatch (ISSUE 17 satellite): on a cpu
backend RouterModel.publish_batch serves from the host matcher (the C++
SubTable, or the oracle Trie when the native plane didn't build)
instead of the XLA program — the round-5 CPU bench measured the XLA
kernel at 0.1x the host matcher on CPU, a regression we used to serve.

``EMQX_TPU_CPU_KERNEL`` is the escape hatch: ``xla`` (what conftest
pins for the rest of the suite) forces the device kernel so CPU CI
still validates it; ``host`` forces the matcher; auto picks the matcher
iff the platform is cpu and no mesh was requested.
"""

import pytest

from emqx_tpu.models.router_model import RouterModel
from emqx_tpu.router.index import ShardedTrieIndex, TrieIndex

FILTERS = [
    ("a/b", 1), ("a/+", 2), ("c/#", 3), ("+/b", 4),
    ("deep/x/y/z/w", 5), ("deep/x/+/z/#", 6), ("$SYS/#", 7), ("#", 8),
]
TOPICS = ["a/b", "c/d/e", "deep/x/y/z/w", "$SYS/broker/uptime",
          "no/match/here", "a"]


def _mk(monkeypatch, mode, index=None):
    monkeypatch.setenv("EMQX_TPU_CPU_KERNEL", mode)
    model = RouterModel(index or TrieIndex(max_levels=8), n_sub_slots=256)
    for f, s in FILTERS:
        model.subscribe(f, s)
    model.aux_register("a/#")
    return model


def test_mode_gates(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_CPU_KERNEL", "host")
    assert RouterModel(TrieIndex())._host_matcher is not None
    monkeypatch.setenv("EMQX_TPU_CPU_KERNEL", "xla")
    assert RouterModel(TrieIndex())._host_matcher is None
    # auto: cpu backend + no mesh → host matcher (conftest pins the
    # whole suite to the cpu platform)
    monkeypatch.delenv("EMQX_TPU_CPU_KERNEL")
    assert RouterModel(TrieIndex())._host_matcher is not None


@pytest.mark.parametrize("index_kind", ["flat", "sharded"])
def test_host_dispatch_parity_with_xla(monkeypatch, index_kind):
    def mk_index():
        return (ShardedTrieIndex(4, max_levels=8)
                if index_kind == "sharded" else TrieIndex(max_levels=8))

    host = _mk(monkeypatch, "host", mk_index())
    xla = _mk(monkeypatch, "xla", mk_index())
    rh = host.publish_batch(TOPICS)
    rx = xla.publish_batch(TOPICS)
    assert [sorted(x) for x in rh[0]] == [sorted(x) for x in rx[0]]
    assert [sorted(x) for x in rh[1]] == [sorted(x) for x in rx[1]]
    assert rh[2] == rx[2]
    assert rh[3] == rx[3] == []
    assert host.launch_count == 0 and host.host_match_count == 1
    assert xla.launch_count == 1 and xla.host_match_count == 0


def test_host_dispatch_tracks_unsubscribe_and_aux(monkeypatch):
    model = _mk(monkeypatch, "host")
    assert sorted(model.publish_batch(["a/b"])[0][0]) == \
        ["#", "+/b", "a/+", "a/b"]
    model.unsubscribe("a/+", 2)
    model.unsubscribe("#", 8)
    assert sorted(model.publish_batch(["a/b"])[0][0]) == ["+/b", "a/b"]
    assert model.publish_batch(["a/b"])[1][0] == ["a/#"]
    model.aux_release("a/#")
    assert model.publish_batch(["a/b"])[1][0] == []


def test_host_dispatch_rides_submit_collect(monkeypatch):
    """The pipeline calls submit/collect, not publish_batch — the host
    path must flow through the same two-stage surface."""
    model = _mk(monkeypatch, "host")
    pending = model.publish_batch_submit(["a/b"])
    matched, aux, slots, fallback = model.publish_batch_collect(pending)
    assert "a/b" in matched[0] and slots[0] and fallback == []


def test_host_dispatch_sys_topics(monkeypatch):
    """MQTT-3.7.2-1: root-level wildcards must not match $-topics on
    the host path either (the C++ SubTable doesn't enforce it; the
    dispatch layer does)."""
    model = _mk(monkeypatch, "host")
    r = model.publish_batch(["$SYS/broker/uptime"])
    assert r[0][0] == ["$SYS/#"]

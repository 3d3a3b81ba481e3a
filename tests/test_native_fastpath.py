"""Native (C++) PUBLISH fast path — the round-4 host data plane.

Covers the correctness seams listed in broker/native_server.py: the
C++ subscription table differentially against the host-oracle trie
(router/trie.py, the emqx_trie.erl semantics), the permit machinery
(slow→fast transition, rules veto, mid-stream rule creation), punt
markers (shared subs, persistent sessions, retained flags, $-topics),
QoS1 with the partitioned packet-id space, no-local, and unsubscribe
teardown. Reference behaviors: emqx_broker.erl:218-232 (publish),
emqx_authz cache (permits), emqx_mqueue.erl (qos1 queue)."""

import asyncio
import random
import time

import pytest

from emqx_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native lib unavailable")

from emqx_tpu.app import BrokerApp            # noqa: E402
from emqx_tpu.broker.native_server import NativeBrokerServer  # noqa: E402
from emqx_tpu.core.message import Message     # noqa: E402
from emqx_tpu.mqtt.client import MqttClient   # noqa: E402


def run(coro):
    asyncio.run(coro)


async def _wait_fast(server, key="fast_in", least=1, timeout=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if server.fast_stats()[key] >= least:
            return True
        await asyncio.sleep(0.05)
    return False


async def _settle(seconds=0.4):
    """Permits grant on the server's next idle poll step."""
    await asyncio.sleep(seconds)


# -- differential: C++ SubTable vs the Python trie oracle --------------------

def _topic_universe(rng, n):
    words = ["a", "b", "c", "dd", "e5", ""]
    topics = []
    for _ in range(n):
        depth = rng.randint(1, 6)
        topics.append("/".join(rng.choice(words) for _ in range(depth)))
    return topics


def test_subtable_matches_python_trie_oracle():
    """Random filters/topics: the C++ table and the host-oracle trie
    (router/trie.py — differentially tested against emqx_trie.erl
    semantics) must return identical match sets."""
    from emqx_tpu.router.trie import Trie

    rng = random.Random(7)
    words = ["a", "b", "c", "dd", "e5", "+", "#", ""]
    filters = set()
    while len(filters) < 400:
        depth = rng.randint(1, 6)
        parts = []
        for lvl in range(depth):
            w = rng.choice(words)
            if w == "#":
                parts.append(w)
                break
            parts.append(w)
        f = "/".join(parts)
        # the python validator's contract: '#' only at the end — the
        # generator above guarantees it
        filters.add(f)
    filters = sorted(filters)

    table = native.NativeSubTable()
    oracle = Trie()
    for i, f in enumerate(filters):
        table.add(i + 1, f)
        oracle.insert(f)

    topics = _topic_universe(rng, 3000)
    for t in topics:
        want = {filters.index(f) + 1 for f in oracle.match(t)}
        got = set(table.match(t))
        assert got == want, (t, sorted(got), sorted(want))

    # removal parity on a random half
    removed = [f for f in filters if rng.random() < 0.5]
    for f in removed:
        assert table.remove(filters.index(f) + 1, f)
        oracle.delete(f)
    for t in topics[:1000]:
        want = {filters.index(f) + 1 for f in oracle.match(t)}
        got = set(table.match(t))
        assert got == want, (t, sorted(got), sorted(want))
    table.close()


def test_subtable_multi_owner_and_upsert():
    table = native.NativeSubTable()
    table.add(1, "x/+", qos=0)
    table.add(2, "x/+", qos=1)
    table.add(1, "x/+", qos=2)          # upsert, not duplicate
    assert sorted(table.match("x/y")) == [1, 2]
    assert table.remove(1, "x/+")
    assert table.match("x/y") == [2]
    assert not table.remove(1, "x/+")   # already gone
    table.close()


# -- end-to-end fast-path semantics ------------------------------------------

def test_fast_transition_and_steady_state():
    """First publish takes the slow path; once the permit lands every
    subsequent publish is handled in C++ — and deliveries stay correct
    across the transition."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="fs")
        await sub.connect()
        await sub.subscribe("ft/+", qos=0)
        pub = MqttClient(port=server.port, clientid="fp")
        await pub.connect()
        for i in range(3):
            await pub.publish("ft/a", f"m{i}".encode(), qos=0)
            m = await sub.recv(timeout=5)
            assert m.payload == f"m{i}".encode()
            await _settle(0.3)
        stats = server.fast_stats()
        assert stats["fast_in"] >= 1, stats   # steady state went native
        await sub.close()
        await pub.close()

    run(main())
    server.stop()


def test_retained_and_sys_topics_punt():
    """retain=1 and $-prefixed topics never fast-path: the retainer
    must store, and $SYS-space semantics stay in Python."""
    app = BrokerApp()
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        pub = MqttClient(port=server.port, clientid="rp")
        await pub.connect()
        sub = MqttClient(port=server.port, clientid="rs")
        await sub.connect()
        await sub.subscribe("rt/+", qos=0)
        # earn the permit on rt/a, then a retained publish on the SAME
        # topic must still go slow (flag checked per-message in C++)
        await pub.publish("rt/a", b"live", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("rt/a", b"keep", qos=0, retain=True)
        await sub.recv(timeout=5)
        await _settle(0.3)
        late = MqttClient(port=server.port, clientid="rl")
        await late.connect()
        await late.subscribe("rt/a", qos=0)
        m = await late.recv(timeout=5)
        assert m.payload == b"keep" and m.retain
        await pub.close(); await sub.close(); await late.close()

    run(main())
    server.stop()


def test_shared_group_native_when_all_members_fast():
    """A $share group whose members are all fast native connections is
    served by the C++ dispatcher (round_robin): normal + group
    deliveries both happen natively once the permit lands."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        normal = MqttClient(port=server.port, clientid="sn")
        await normal.connect()
        await normal.subscribe("st/x", qos=0)
        member = MqttClient(port=server.port, clientid="sm")
        await member.connect()
        await member.subscribe("$share/g1/st/x", qos=0)
        pub = MqttClient(port=server.port, clientid="sp")
        await pub.connect()
        for i in range(3):
            await pub.publish("st/x", f"s{i}".encode(), qos=0)
            await _settle(0.2)
        # normal sub saw all three; group member saw all three (single
        # member) — and the steady state ran in C++
        for i in range(3):
            m = await normal.recv(timeout=5)
            assert m.payload == f"s{i}".encode()
            g = await member.recv(timeout=5)
            assert g.payload == f"s{i}".encode()
        stats = server.fast_stats()
        assert stats["fast_in"] >= 1 and stats["shared_dispatch"] >= 1, stats
        await normal.close(); await member.close(); await pub.close()

    run(main())
    server.stop()


def test_shared_group_round_robin_rotates_natively():
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        m1 = MqttClient(port=server.port, clientid="rr1")
        await m1.connect(); await m1.subscribe("$share/g/rr/t", qos=0)
        m2 = MqttClient(port=server.port, clientid="rr2")
        await m2.connect(); await m2.subscribe("$share/g/rr/t", qos=0)
        pub = MqttClient(port=server.port, clientid="rrp")
        await pub.connect()
        await pub.publish("rr/t", b"warm", qos=0)
        await _settle()
        for i in range(8):
            await pub.publish("rr/t", f"n{i}".encode(), qos=0)

        async def drain(c):
            got = []
            while True:
                try:
                    got.append((await c.recv(timeout=0.5)).payload)
                except asyncio.TimeoutError:
                    return got
        g1, g2 = await drain(m1), await drain(m2)
        assert len(g1) + len(g2) == 9, (g1, g2)
        assert abs(len(g1) - len(g2)) <= 2        # rotating, not sticky
        assert server.fast_stats()["shared_dispatch"] >= 8
        await m1.close(); await m2.close(); await pub.close()

    run(main())
    server.stop()


def test_shared_group_mixed_membership_punts():
    """One persistent-session member makes the whole group punt: the
    Python SharedSub owns dispatch (its mqueue/offline semantics)."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        fast = MqttClient(port=server.port, clientid="mxf")
        await fast.connect()
        await fast.subscribe("$share/g/mx/t", qos=0)
        persist = MqttClient(port=server.port, clientid="mxp",
                             clean_start=False, proto_ver=5,
                             properties={"Session-Expiry-Interval": 300})
        await persist.connect()
        await persist.subscribe("$share/g/mx/t", qos=0)
        pub = MqttClient(port=server.port, clientid="mxpub")
        await pub.connect()
        for i in range(4):
            await pub.publish("mx/t", f"p{i}".encode(), qos=0)
            await _settle(0.2)
        stats = server.fast_stats()
        assert stats["shared_dispatch"] == 0, stats  # group stayed punted

        async def drain(c):
            got = []
            while True:
                try:
                    got.append((await c.recv(timeout=0.5)).payload)
                except asyncio.TimeoutError:
                    return got
        g1, g2 = await drain(fast), await drain(persist)
        assert len(g1) + len(g2) == 4, (g1, g2)   # each msg exactly once
        await fast.close(); await persist.close(); await pub.close()

    run(main())
    server.stop()


def test_shared_strategy_change_moves_groups_off_native():
    """Only round_robin runs in C++: flipping the strategy reconciles
    live groups back onto the Python dispatcher."""
    from emqx_tpu.config.config import Config
    conf = Config()
    conf.init_load("")
    app = BrokerApp.from_config(conf)
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        m1 = MqttClient(port=server.port, clientid="sc1")
        await m1.connect(); await m1.subscribe("$share/g/sc/t", qos=0)
        pub = MqttClient(port=server.port, clientid="scp")
        await pub.connect()
        await pub.publish("sc/t", b"w", qos=0)
        await m1.recv(timeout=5)
        await _settle()
        await pub.publish("sc/t", b"n", qos=0)
        await m1.recv(timeout=5)
        assert await _wait_fast(server, "shared_dispatch", 1)
        base = server.fast_stats()["shared_dispatch"]
        conf.put("shared_subscription_strategy", "sticky")
        await _settle(0.3)
        for i in range(3):
            await pub.publish("sc/t", f"s{i}".encode(), qos=0)
            m = await m1.recv(timeout=5)
            assert m.payload == f"s{i}".encode()
            await _settle(0.15)
        assert server.fast_stats()["shared_dispatch"] == base, \
            "sticky strategy must not dispatch natively"
        await m1.close(); await pub.close()

    run(main())
    server.stop()


async def _wait_hits(hits, n, timeout=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if len(hits) >= n:
            return True
        await asyncio.sleep(0.05)
    return False


def test_tap_batches_survive_mid_batch_flush_intact():
    """Round-7 regression: a tap batch that overflows the flush cap
    mid-cycle must re-seed the record-header slot before the next
    entry — the first post-flush entry used to land at offset 0 and be
    OVERWRITTEN by the header patch, corrupting every boundary-crossing
    batch. A small max_packet_size shrinks the cap (max_size/2+1) so a
    few hundred fat-payload messages cross many boundaries; every
    entry must reach the rules with its exact topic AND payload."""
    app = BrokerApp()
    hits = []
    app.rules.register_action("sink", lambda cols, a: hits.append(cols))
    app.rules.create_rule("r-tapcap",
                          'SELECT topic, payload FROM "fat/#"',
                          [{"function": "sink", "args": {}}])
    server = NativeBrokerServer(port=0, app=app, max_packet_size=4096)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="fs")
        await sub.connect()
        await sub.subscribe("fat/+", qos=0)
        pub = MqttClient(port=server.port, clientid="fp")
        await pub.connect()
        await pub.publish("fat/t", b"warm", qos=0)     # earns the permit
        await sub.recv(timeout=5)
        await _settle(0.8)
        n = 300
        for i in range(n):
            # ~200B distinct payloads: entries ~230B vs a ~2KB cap →
            # a flush boundary every ~8 entries
            await pub.publish("fat/t", (b"p%04d-" % i) + b"x" * 200,
                              qos=0)
            await sub.recv(timeout=5)
        assert await _wait_fast(server, "taps", n)
        assert await _wait_hits(hits, n + 1, timeout=15), len(hits)
        assert server.tap_dropped == 0
        got = sorted(h["payload"] for h in hits
                     if h["payload"] != b"warm")
        want = sorted((b"p%04d-" % i) + b"x" * 200 for i in range(n))
        assert got == want        # exact topics/payloads, no corruption
        assert all(h["topic"] == "fat/t" for h in hits)
        await sub.close()
        await pub.close()

    run(main())
    server.stop()


def test_ruled_topics_stay_fast_via_taps_and_rules_see_everything():
    """Round-5 contract (VERDICT r4 #5): rules must see EVERY matching
    message WITHOUT de-permitting the fast path. Rule FROM filters
    mirror into the C++ table as non-delivering tap entries; a ruled
    topic still earns its permit, deliveries run natively, and every
    fast-path message is copied to the rule runtime (taps counter).
    Creating a rule mid-stream flushes permits AND installs its tap
    before re-grant, so no message is missed across the transition."""
    app = BrokerApp()
    hits = []
    app.rules.register_action("sink", lambda cols, a: hits.append(cols))
    app.rules.create_rule("r-pre", 'SELECT topic FROM "ruled/#"',
                          [{"function": "sink", "args": {}}])
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="qs")
        await sub.connect()
        await sub.subscribe("ruled/+", qos=0)
        await sub.subscribe("free/+", qos=0)
        pub = MqttClient(port=server.port, clientid="qp")
        await pub.connect()
        # ruled topic: first publish slow (earns permit), then native —
        # and the rule fires for EVERY message either way
        for i in range(5):
            await pub.publish("ruled/t", b"x", qos=0)
            await sub.recv(timeout=5)
            await _settle(0.2)
        assert await _wait_hits(hits, 5), len(hits)
        assert await _wait_fast(server, "fast_in", 1)   # went native
        assert await _wait_fast(server, "taps", 1)      # and was tapped
        # a rule created mid-stream over an already-fast topic installs
        # its tap before the permit flush's re-grants: no missed message
        await pub.publish("free/t", b"f0", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("free/t", b"f1", qos=0)
        await sub.recv(timeout=5)
        app.rules.create_rule("r-live", 'SELECT topic FROM "free/#"',
                              [{"function": "sink", "args": {}}])
        n_before = len(hits)
        await _settle(0.3)
        for i in range(3):
            await pub.publish("free/t", b"f%d" % (2 + i), qos=0)
            await sub.recv(timeout=5)
            await _settle(0.2)
        assert await _wait_hits(hits, n_before + 3), \
            (len(hits), n_before)
        # deleting every rule removes the taps; the plane stays fast
        app.rules.delete_rule("r-pre")
        app.rules.delete_rule("r-live")
        await _settle(0.3)
        taps_before = server.fast_stats()["taps"]
        await pub.publish("ruled/t", b"y", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("ruled/t", b"z", qos=0)
        await sub.recv(timeout=5)
        await _settle(0.2)
        assert server.fast_stats()["taps"] == taps_before
        assert server.tap_dropped == 0
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_qos1_native_path_pid_partition():
    """QoS1 publish → native PUBACK to the publisher; QoS1 delivery →
    native pid >= 32768, acked by the client and consumed in C++."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="q1s")
        await sub.connect()
        await sub.subscribe("q1/t", qos=1)
        pub = MqttClient(port=server.port, clientid="q1p")
        await pub.connect()
        await pub.publish("q1/t", b"w", qos=1)   # slow path, earns permit
        m0 = await sub.recv(timeout=5)
        assert m0.packet_id is not None and m0.packet_id < 32768
        await _settle()
        for i in range(5):
            await pub.publish("q1/t", f"n{i}".encode(), qos=1)
        got = [await sub.recv(timeout=5) for _ in range(5)]
        assert [g.payload for g in got] == [f"n{i}".encode()
                                           for i in range(5)]
        for g in got:
            assert g.qos == 1 and g.packet_id >= 32768, g
        stats = server.fast_stats()
        assert stats["fast_in"] >= 5 and stats["fast_out"] >= 5
        assert await _wait_fast(server, "native_acks", 5)
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_no_local_honored_natively():
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        c = MqttClient(port=server.port, clientid="nl1", proto_ver=5)
        await c.connect()
        await c.subscribe("nl/t", qos=0, nl=1)
        other = MqttClient(port=server.port, clientid="nl2", proto_ver=5)
        await other.connect()
        await other.subscribe("nl/t", qos=0)
        await c.publish("nl/t", b"first", qos=0)     # slow path
        assert (await other.recv(timeout=5)).payload == b"first"
        await _settle()
        await c.publish("nl/t", b"second", qos=0)    # fast path
        assert (await other.recv(timeout=5)).payload == b"second"
        with pytest.raises(asyncio.TimeoutError):
            await c.recv(timeout=0.6)                # no-local: no echo
        await c.close(); await other.close()

    run(main())
    server.stop()


def test_unsubscribe_removes_native_entry():
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="us")
        await sub.connect()
        await sub.subscribe("ut/+", qos=0)
        pub = MqttClient(port=server.port, clientid="up")
        await pub.connect()
        await pub.publish("ut/a", b"m0", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("ut/a", b"m1", qos=0)      # fast
        await sub.recv(timeout=5)
        await sub.unsubscribe("ut/+")
        await _settle(0.3)
        await pub.publish("ut/a", b"m2", qos=0)      # fast, no targets
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(timeout=0.6)
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_persistent_session_subscriber_stays_on_python_path():
    """clean_start=False subscribers punt: their mqueue/inflight state
    must stay authoritative in the Python session (offline queueing)."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="ps",
                         clean_start=False, proto_ver=5,
                         properties={"Session-Expiry-Interval": 300})
        await sub.connect()
        await sub.subscribe("pt/t", qos=1)
        pub = MqttClient(port=server.port, clientid="pp")
        await pub.connect()
        for i in range(3):
            await pub.publish("pt/t", f"p{i}".encode(), qos=1)
            m = await sub.recv(timeout=5)
            assert m.payload == f"p{i}".encode()
            assert m.packet_id is None or m.packet_id < 32768
            await _settle(0.2)
        assert server.fast_stats()["fast_in"] == 0
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_fast_metrics_merge_into_node_metrics():
    app = BrokerApp()
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="ms")
        await sub.connect()
        await sub.subscribe("mm/t", qos=0)
        pub = MqttClient(port=server.port, clientid="mp")
        await pub.connect()
        await pub.publish("mm/t", b"0", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        for i in range(10):
            await pub.publish("mm/t", b"x", qos=0)
        for i in range(10):
            await sub.recv(timeout=5)
        before = app.metrics.val("messages.received")
        server._merge_fast_metrics()
        after = app.metrics.val("messages.received")
        assert after - before >= 10
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_rewrite_topics_never_earn_permits():
    """A topic matching a pub rewrite rule must stay on the slow path —
    a native fan-out on the raw topic would bypass the redirect
    (round-4 review finding: _slow_consumers_watch must cover
    services/rewrite.py)."""
    app = BrokerApp()
    app.rewrite.add_rule("publish", "raw/#", r"^raw/(.+)$", "cooked/$1")
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="ws")
        await sub.connect()
        await sub.subscribe("cooked/+", qos=0)
        pub = MqttClient(port=server.port, clientid="wp")
        await pub.connect()
        for i in range(3):
            await pub.publish("raw/x", f"r{i}".encode(), qos=0)
            m = await sub.recv(timeout=5)
            assert m.topic == "cooked/x" and m.payload == f"r{i}".encode()
            await _settle(0.2)
        assert server.fast_stats()["fast_in"] == 0
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_two_share_groups_punt_markers_are_independent():
    """Two punt-mode $share groups over one real topic own separate
    punt state; unsubscribing one group must NOT remove the marker the
    other still needs (round-4 review finding). A persistent-session
    member keeps both groups in punt mode (not natively served)."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        m1 = MqttClient(port=server.port, clientid="g1m",
                        clean_start=False, proto_ver=5,
                        properties={"Session-Expiry-Interval": 300})
        await m1.connect()
        await m1.subscribe("$share/ga/sh/t", qos=0)
        await m1.subscribe("$share/gb/sh/t", qos=0)
        pub = MqttClient(port=server.port, clientid="gpb")
        await pub.connect()
        await pub.publish("sh/t", b"both", qos=0)
        # one member in each group: two deliveries
        assert (await m1.recv(timeout=5)).payload == b"both"
        assert (await m1.recv(timeout=5)).payload == b"both"
        await m1.unsubscribe("$share/ga/sh/t")
        await _settle(0.3)
        for i in range(3):
            await pub.publish("sh/t", f"x{i}".encode(), qos=0)
            m = await m1.recv(timeout=5)
            assert m.payload == f"x{i}".encode()
            await _settle(0.15)
        # the surviving group still punts every publish (persistent
        # member => never native)
        stats = server.fast_stats()
        assert stats["fast_in"] == 0 and stats["shared_dispatch"] == 0
        await m1.close(); await pub.close()

    run(main())
    server.stop()


def test_config_driven_native_listener():
    """listeners { n1 { type = native } } boots the C++ host through
    the standard listener supervisor, data plane included."""
    from emqx_tpu.config.config import Config

    conf = Config()
    conf.init_load(
        'listeners { nat { type = native, bind = "127.0.0.1:0" } }')
    app = BrokerApp.from_config(conf)

    async def main():
        ids = await app.listeners.start_all(conf.get("listeners"))
        assert ids == ["native:nat"]
        lst = app.listeners.find("native:nat")
        sub = MqttClient(port=lst.port, clientid="cs")
        await sub.connect()
        await sub.subscribe("cl/+", qos=0)
        pub = MqttClient(port=lst.port, clientid="cp")
        await pub.connect()
        await pub.publish("cl/a", b"m0", qos=0)
        assert (await sub.recv(timeout=5)).payload == b"m0"
        await _settle()
        await pub.publish("cl/a", b"m1", qos=0)
        assert (await sub.recv(timeout=5)).payload == b"m1"
        assert lst.fast_stats()["fast_in"] >= 1
        info = app.listeners.info()
        assert info[0]["type"] == "native" and info[0]["running"]
        await sub.close(); await pub.close()
        await app.listeners.stop_all()

    run(main())


def test_clustered_node_keeps_fast_path_with_remote_punts():
    """A clustered node keeps its C++ data plane: topics with a remote
    audience punt (the route observer mirrors remote routes as
    markers) and get forwarded; local-only topics stay native."""
    from emqx_tpu.cluster.harness import make_cluster, stop, sync
    from emqx_tpu.mqtt import packet as P

    nodes = make_cluster(2)
    n1, n2 = nodes
    server = NativeBrokerServer(port=0, app=n1.app)
    server.start()

    async def main():
        # remote subscriber on node2 via the cluster plane
        ch = _cluster_channel(n2, "rsub")
        ch.handle_in(P.Subscribe(packet_id=1,
                                 topic_filters=[("far/t", {"qos": 0})]))
        sync(nodes)
        assert n1.app.broker.router.has_route("far/t", "node2")

        pub = MqttClient(port=server.port, clientid="np")
        await pub.connect()
        loc = MqttClient(port=server.port, clientid="nl")
        await loc.connect()
        await loc.subscribe("near/t", qos=0)

        # remote-audience topic: every publish punts + forwards
        for i in range(3):
            await pub.publish("far/t", f"f{i}".encode(), qos=0)
            await _settle(0.2)
        got = [p for p in ch.outbox if isinstance(p, P.Publish)]
        assert [p.payload for p in got] == [b"f0", b"f1", b"f2"]

        # local-only topic: still rides the fast path
        await pub.publish("near/t", b"n0", qos=0)
        await loc.recv(timeout=5)
        await _settle()
        await pub.publish("near/t", b"n1", qos=0)
        await loc.recv(timeout=5)
        assert server.fast_stats()["fast_in"] >= 1
        await pub.close(); await loc.close()

    def _cluster_channel(node, clientid):
        from emqx_tpu.broker.channel import Channel

        outbox = []
        ch = Channel(node.app.broker, node.app.cm,
                     send=lambda pkts: outbox.extend(pkts))
        ch.outbox = outbox
        out = ch.handle_in(P.Connect(clientid=clientid, proto_ver=P.MQTT_V5,
                                     clean_start=True))
        assert out[0].reason_code == P.RC_SUCCESS
        return ch

    try:
        run(main())
    finally:
        server.stop()
        stop(nodes)


def test_cross_transport_subscriber_always_served():
    """One app, two transports: a subscriber on the asyncio server must
    receive publishes from a native-listener client forever — its punt
    marker keeps those topics off the native fan-out."""
    from emqx_tpu.broker.server import BrokerServer

    app = BrokerApp()
    nat = NativeBrokerServer(port=0, app=app)
    nat.start()

    async def main():
        aio = BrokerServer(port=0, app=app)
        await aio.start()
        sub_aio = MqttClient(port=aio.port, clientid="xa")
        await sub_aio.connect()
        await sub_aio.subscribe("xt/+", qos=0)
        sub_nat = MqttClient(port=nat.port, clientid="xn")
        await sub_nat.connect()
        await sub_nat.subscribe("xt/+", qos=0)
        pub = MqttClient(port=nat.port, clientid="xp")
        await pub.connect()
        for i in range(4):
            await pub.publish("xt/k", f"x{i}".encode(), qos=0)
            a = await sub_aio.recv(timeout=5)
            n = await sub_nat.recv(timeout=5)
            assert a.payload == n.payload == f"x{i}".encode()
            await _settle(0.2)
        # the asyncio subscriber's punt marker kept the topic slow
        assert nat.fast_stats()["fast_in"] == 0
        await sub_aio.unsubscribe("xt/+")
        await _settle(0.3)
        # with the cross-transport audience gone, the topic can go fast
        await pub.publish("xt/k", b"solo0", qos=0)
        assert (await sub_nat.recv(timeout=5)).payload == b"solo0"
        await _settle()
        await pub.publish("xt/k", b"solo1", qos=0)
        assert (await sub_nat.recv(timeout=5)).payload == b"solo1"
        assert await _wait_fast(nat, "fast_in", 1)
        await sub_aio.close(); await sub_nat.close(); await pub.close()
        await aio.stop()

    run(main())
    nat.stop()


def test_per_topic_ordering_across_permit_transition():
    """A publisher's stream must arrive in order even as its topic
    moves slow→fast mid-stream (permits only apply once the pipeline
    is idle, and host.send enqueues FIFO ahead of fast deliveries)."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="os")
        await sub.connect()
        await sub.subscribe("ord/t", qos=0)
        pub = MqttClient(port=server.port, clientid="op")
        await pub.connect()
        n = 300
        for i in range(n):
            await pub.publish("ord/t", b"%04d" % i, qos=0)
            if i == 20:
                await _settle(0.3)   # let the permit land mid-stream
        got = [await sub.recv(timeout=10) for _ in range(n)]
        assert [g.payload for g in got] == [b"%04d" % i for i in range(n)]
        assert server.fast_stats()["fast_in"] > 0   # transition happened
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_qos2_stays_on_python_path_until_safe():
    """The round-6 native ack plane owns QoS2 only behind the same
    permit/punt seams as QoS0/1: an UNPERMITTED topic and a topic with
    a punt-class audience (persistent session) must keep the full
    exchange in the Python session — exactly-once state cannot split
    planes mid-audience (tests/test_native_qos2.py covers the native
    side of the seam)."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="q2s")
        await sub.connect()
        await sub.subscribe("q2/t", qos=2)
        # the persistent-session subscriber makes q2/t punt-marked
        ps = MqttClient(port=server.port, clientid="q2-ps",
                        clean_start=False, proto_ver=5,
                        properties={"Session-Expiry-Interval": 60})
        await ps.connect()
        await ps.subscribe("q2/t", qos=2)
        pub = MqttClient(port=server.port, clientid="q2p")
        await pub.connect()
        # no permit yet AND punt audience: every qos2 publish runs the
        # Python exchange (python pids < 32768 toward the subscribers)
        fast0 = server.fast_stats()["fast_in"]
        for i in range(3):
            await pub.publish("q2/t", f"e{i}".encode(), qos=2)
            m = await sub.recv(timeout=5)
            assert m.payload == f"e{i}".encode() and m.qos == 2
            assert m.packet_id < 32768          # python session pid
            mp = await ps.recv(timeout=5)
            assert mp.payload == f"e{i}".encode()
            await _settle(0.2)
        assert server.fast_stats()["fast_in"] == fast0, "qos2 fast-pathed"
        await sub.close(); await ps.close(); await pub.close()

    run(main())
    server.stop()


def test_trace_start_flushes_permits_immediately():
    """Starting a topic trace must immediately pull already-fast topics
    back through Python — a debugging trace cannot wait out the permit
    TTL before seeing messages."""
    app = BrokerApp()
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="ts")
        await sub.connect()
        await sub.subscribe("tr/t", qos=0)
        pub = MqttClient(port=server.port, clientid="tp")
        await pub.connect()
        await pub.publish("tr/t", b"w", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("tr/t", b"fast", qos=0)
        await sub.recv(timeout=5)
        assert await _wait_fast(server, "fast_in", 1)
        base = server.fast_stats()["fast_in"]
        app.trace.start("t1", "topic", "tr/#")
        await _settle(0.3)
        for i in range(3):
            await pub.publish("tr/t", f"tr{i}".encode(), qos=0)
            assert (await sub.recv(timeout=5)).payload == f"tr{i}".encode()
            await _settle(0.15)
        assert server.fast_stats()["fast_in"] == base, \
            "traced topic still on the fast path"
        tr = app.trace.traces["t1"]
        assert len(tr.lines) >= 1, "trace captured nothing"
        # stopping the trace frees the topic again
        app.trace.stop("t1")
        await _settle(0.3)
        await pub.publish("tr/t", b"free0", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("tr/t", b"free1", qos=0)
        await sub.recv(timeout=5)
        assert await _wait_fast(server, "fast_in", base + 1)
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_duplicate_subscribe_punt_ref_stays_single():
    """Duplicate SUBSCRIBE on a punt-shaped subscription (here: a
    persistent session, the shape a session resume re-fires for every
    restored sub) must not double-count the punt ref — round-4 advisor
    finding: the single ref drop at UNSUBSCRIBE then left the marker in
    the C++ table forever and leaked punt tokens under clientid churn."""
    server = NativeBrokerServer(port=0, app=BrokerApp())
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="dup-ps",
                         clean_start=False, proto_ver=5,
                         properties={"Session-Expiry-Interval": 300})
        await sub.connect()
        await sub.subscribe("dup/t", qos=1)
        await sub.subscribe("dup/t", qos=1)     # duplicate SUBSCRIBE
        await _settle(0.3)
        assert server._punt_refs and max(
            server._punt_refs.values()) == 1, server._punt_refs
        assert server._token_refs.get("c:dup-ps", 0) == 1
        await sub.unsubscribe("dup/t")
        await _settle(0.3)
        # ONE unsubscribe fully clears the marker and the token refs
        assert not server._punt_refs, server._punt_refs
        assert "c:dup-ps" not in server._token_refs
        await sub.close()

    run(main())
    server.stop()


def test_message_event_rule_blocks_all_permits():
    """A rule on $events/message_delivered consumes per-delivery events
    that only the Python plane fires: while it exists NO topic may hold
    a fast-path permit, or the rule silently misses every fast-path
    delivery (round-4 advisor finding). Creating the rule mid-stream
    must also flush already-granted permits."""
    app = BrokerApp()
    hits = []
    app.rules.register_action("sink", lambda cols, a: hits.append(cols))
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="evs")
        await sub.connect()
        await sub.subscribe("ev/+", qos=0)
        pub = MqttClient(port=server.port, clientid="evp")
        await pub.connect()
        # earn a permit on a rule-free topic
        await pub.publish("ev/t", b"0", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("ev/t", b"1", qos=0)
        await sub.recv(timeout=5)
        assert await _wait_fast(server, "fast_in", 1)
        # a delivered-event rule appears: permits flush, and every
        # subsequent delivery fires the rule (i.e. went through Python)
        app.rules.create_rule(
            "r-ev", 'SELECT topic FROM "$events/message_delivered"',
            [{"function": "sink", "args": {}}])
        await _settle(0.3)
        fast_before = server.fast_stats()["fast_in"]
        n_before = len(hits)
        for i in range(3):
            await pub.publish("ev/t", f"e{i}".encode(), qos=0)
            m = await sub.recv(timeout=5)
            assert m.payload == f"e{i}".encode()
            await _settle(0.2)
        assert len(hits) == n_before + 3, "event rule missed deliveries"
        assert server.fast_stats()["fast_in"] == fast_before
        # deleting the rule re-opens the fast path
        app.rules.delete_rule("r-ev")
        await _settle(0.3)
        await pub.publish("ev/t", b"again", qos=0)
        await sub.recv(timeout=5)
        await _settle()
        await pub.publish("ev/t", b"fast", qos=0)
        await sub.recv(timeout=5)
        assert await _wait_fast(server, "fast_in", fast_before + 1)
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_shared_pick_buffer_overflow_and_empty_groups():
    """shared_pick's count and buffer must never desync (round-4
    advisor finding: n advanced even when no pair was written). More
    pickable groups than the buffer holds → the overflowing call writes
    nothing and advances no cursor; the resized retry returns them all,
    exactly once per group (a partial first pass would double-rotate).
    Groups with all members removed are skipped, not emitted as
    garbage."""
    tab = native.NativeSubTable()
    n_groups = 400                       # > the 512-u64 buffer's 256 pairs
    for g in range(1, n_groups + 1):
        tab.shared_add(g, g * 10, "of/+")
        tab.shared_add(g, g * 10 + 1, "of/+")
    # a few emptied groups interleaved: token present, no members
    for g in (5, 77, 300):
        tab.shared_del(g, g * 10, "of/+")
        tab.shared_del(g, g * 10 + 1, "of/+")
    picks = tab.shared_pick("of/x")
    tokens = sorted(p[0] for p in picks)
    want = sorted(g for g in range(1, n_groups + 1) if g not in (5, 77, 300))
    assert tokens == want, (len(tokens), len(want))
    for tok, owner in picks:
        assert owner in (tok * 10, tok * 10 + 1), (tok, owner)
    # each group's cursor advanced EXACTLY once despite the overflow
    # retry: the next pick must rotate to the other 2-member slot
    first = dict(picks)
    for tok, owner in tab.shared_pick("of/x"):
        assert owner != first[tok], (tok, owner, "cursor double-advanced")
    tab.close()


# -- device match lane (VERDICT r4 #2: the device router ON the C++ plane) ---

def _lane_app():
    from emqx_tpu.config.config import Config
    from emqx_tpu.app import BrokerApp

    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.min_batch", 0)
    return BrokerApp.from_config(conf)


def test_device_lane_end_to_end():
    """Permitted publishes ride the device matcher and fan out in C++:
    lane_in/lane_out advance, qos1 gets a native PUBACK and a pid in
    the native space, and a 150-message burst on one topic arrives in
    order (per-topic FIFO through park → device batch → response)."""
    server = NativeBrokerServer(port=0, app=_lane_app(), device_lane="on")
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="dls")
        await sub.connect()
        await sub.subscribe("dl/+", qos=1)
        pub = MqttClient(port=server.port, clientid="dlp")
        await pub.connect()
        await pub.publish("dl/t", b"warm", qos=0)   # slow path, earns permit
        await sub.recv(timeout=20)
        await _settle(0.5)
        for i in range(4):
            await pub.publish("dl/t", f"q{i}".encode(), qos=1)
            m = await sub.recv(timeout=20)
            assert m.payload == f"q{i}".encode()
            assert m.packet_id is None or m.packet_id >= 32768, m.packet_id
            await asyncio.sleep(0.1)
        st = server.fast_stats()
        assert st["lane_in"] >= 1 and st["lane_out"] >= 1, st
        assert st["native_acks"] >= 1, st
        for i in range(150):
            await pub.publish("dl/t", str(i).encode(), qos=0)
        got = [int((await sub.recv(timeout=20)).payload)
               for _ in range(150)]
        assert got == list(range(150)), got[:10]
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_device_lane_punts_on_punt_class_subscriber():
    """A punt-shaped subscriber (persistent session) joining a laned
    topic flips delivery back to the complete Python fan-out: both the
    native and the punt subscriber receive. The punt is SYNCHRONOUS
    (TryFast consults the punt-only trie before parking — no wasted
    device round trip), so the generic punts counter advances; the
    lane-response punt branch itself is exercised by the sanitizer
    lane driver's flagged responses."""
    server = NativeBrokerServer(port=0, app=_lane_app(), device_lane="on")
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="dps")
        await sub.connect()
        await sub.subscribe("dp/t", qos=0)
        pub = MqttClient(port=server.port, clientid="dpp")
        await pub.connect()
        await pub.publish("dp/t", b"w", qos=0)
        await sub.recv(timeout=20)
        await _settle(0.5)
        await pub.publish("dp/t", b"laned", qos=0)
        await sub.recv(timeout=20)
        assert await _wait_fast(server, "lane_out", 1)
        ps = MqttClient(port=server.port, clientid="dp-ps",
                        clean_start=False, proto_ver=5,
                        properties={"Session-Expiry-Interval": 60})
        await ps.connect()
        await ps.subscribe("dp/t", qos=0)
        await _settle(0.4)
        punts0 = server.fast_stats()["punts"]
        await pub.publish("dp/t", b"both", qos=0)
        assert (await sub.recv(timeout=20)).payload == b"both"
        assert (await ps.recv(timeout=20)).payload == b"both"
        assert await _wait_fast(server, "punts", punts0 + 1)
        await sub.close(); await pub.close(); await ps.close()

    run(main())
    server.stop()


def test_device_lane_disable_drains_to_python():
    """Turning the lane off mid-stream must lose nothing: parked frames
    drain to the Python path in order and delivery continues."""
    server = NativeBrokerServer(port=0, app=_lane_app(), device_lane="on")
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="dds")
        await sub.connect()
        await sub.subscribe("dd/t", qos=0)
        pub = MqttClient(port=server.port, clientid="ddp")
        await pub.connect()
        await pub.publish("dd/t", b"w", qos=0)
        await sub.recv(timeout=20)
        await _settle(0.5)
        for i in range(30):
            await pub.publish("dd/t", str(i).encode(), qos=0)
        server._set_lane(False)        # drains parked frames to Python
        got = [int((await sub.recv(timeout=20)).payload)
               for _ in range(30)]
        assert got == list(range(30)), got[:10]
        # lane off: further traffic walks in C++ (fast_in grows, lane_in
        # stays put)
        lane_in = server.fast_stats()["lane_in"]
        await pub.publish("dd/t", b"walked", qos=0)
        assert (await sub.recv(timeout=20)).payload == b"walked"
        await _settle(0.2)
        assert server.fast_stats()["lane_in"] == lane_in
        assert server.host.lane_backlog() == 0
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_device_lane_recompiles_parked_when_tables_grow():
    """The lane opens only after every program it can launch is
    compiled, and tables that outgrow those shapes park it (parked
    frames drain to Python, in order) until the new shapes compile —
    no frame ever waits on a compile, so the stale deadline never
    trips, and delivery stays complete and ordered throughout."""
    app = _lane_app()
    model = app.broker.model
    server = NativeBrokerServer(port=0, app=app, device_lane="on")
    server.start()
    assert server.lane_open.is_set()
    assert "step/16384" in server.lane_compile_s

    async def main():
        sub = MqttClient(port=server.port, clientid="dgs")
        await sub.connect()
        await sub.subscribe("dg/+", qos=0)
        pub = MqttClient(port=server.port, clientid="dgp")
        await pub.connect()
        await pub.publish("dg/t", b"w", qos=0)   # slow path, earns permit
        await sub.recv(timeout=20)
        await _settle(0.5)
        await pub.publish("dg/t", b"laned", qos=0)
        assert (await sub.recv(timeout=20)).payload == b"laned"
        assert await _wait_fast(server, "lane_out", 1)
        shapes = model._table_shapes()
        for i in range(3000):                     # outgrow the tables
            model.subscribe(f"grow/{i}/leaf", i % 64)
        for i in range(40):
            await pub.publish("dg/t", str(i).encode(), qos=0)
        got = [int((await sub.recv(timeout=30)).payload) for _ in range(40)]
        assert got == list(range(40)), got[:10]
        deadline = time.monotonic() + 60
        while not server.lane_open.is_set():
            assert time.monotonic() < deadline, "lane never reopened"
            await asyncio.sleep(0.05)
        assert model._table_shapes() != shapes
        # the drain revoked the topic's permit: re-earn it, then lane
        await pub.publish("dg/t", b"re-earn", qos=0)
        assert (await sub.recv(timeout=20)).payload == b"re-earn"
        await _settle(0.5)
        out0 = server.fast_stats()["lane_out"]
        await pub.publish("dg/t", b"after", qos=0)
        assert (await sub.recv(timeout=20)).payload == b"after"
        assert await _wait_fast(server, "lane_out", out0 + 1)
        assert server.fast_stats()["lane_stale"] == 0
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_match_filter_union_equals_walk():
    """Differential: for random topics, the union of MatchFilter over
    the oracle's matched filters must equal the walk's match set — the
    invariant the device lane's delivery correctness rests on."""
    from emqx_tpu.router.trie import Trie

    rng = random.Random(11)
    words = ["a", "b", "cc", "d4", "+", "#", ""]
    filters = set()
    while len(filters) < 300:
        parts = []
        for _ in range(rng.randint(1, 6)):
            w = rng.choice(words)
            parts.append(w)
            if w == "#":
                break
        filters.add("/".join(parts))
    filters = sorted(filters)
    table = native.NativeSubTable()
    oracle = Trie()
    for i, f in enumerate(filters):
        table.add(i + 1, f)
        oracle.insert(f)
    for t in _topic_universe(random.Random(12), 2000):
        want = set(table.match(t))
        got = set()
        for f in oracle.match(t):
            got.update(table.match_filter(f))
        assert got == want, (t, sorted(got), sorted(want))
    table.close()


def test_max_qos_cap_enforced_on_fast_path():
    """mqtt.max_qos_allowed must hold even after a topic earns a C++
    permit: an over-cap qos1 publish skips the fast path and gets the
    channel's DISCONNECT 0x9B, never a native PUBACK (round-5 review
    finding)."""
    from emqx_tpu.config.config import Config
    from emqx_tpu.mqtt import packet as P

    conf = Config()
    conf.put("mqtt.max_qos_allowed", 0)
    app = BrokerApp.from_config(conf)
    server = NativeBrokerServer(port=0, app=app)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="mqs")
        await sub.connect()
        await sub.subscribe("cap/t", qos=0)
        pub = MqttClient(port=server.port, clientid="mqp", proto_ver=5)
        await pub.connect()
        # earn the permit at qos0
        for i in range(2):
            await pub.publish("cap/t", f"m{i}".encode(), qos=0)
            await sub.recv(timeout=5)
            await _settle(0.3)
        assert server.fast_stats()["fast_in"] >= 1
        # over-cap publish: raw send (the helper would await a PUBACK
        # that the refusal replaces with DISCONNECT)
        await pub._send(P.Publish(topic="cap/t", payload=b"q1", qos=1,
                                  packet_id=7, properties={}))
        pkt = await pub._expect(P.DISCONNECT, 5)
        assert pkt.reason_code == P.RC_QOS_NOT_SUPPORTED, hex(pkt.reason_code)
        await sub.close(); await pub.close()

    run(main())
    server.stop()


def test_lane_ruled_and_subscribed_filter_delivers_once():
    """Round-5 review finding: a filter that is BOTH subscribed and a
    rule FROM filter appears in the lane response's matched and aux
    lists — without dedup the C++ side delivered the message twice.
    Exactly-once delivery + the rule still firing is the contract."""
    app = _lane_app()
    hits = []
    app.rules.register_action("sink", lambda cols, a: hits.append(cols))
    app.rules.create_rule("same", 'SELECT topic FROM "sr/#"',
                          [{"function": "sink", "args": {}}])
    server = NativeBrokerServer(port=0, app=app, device_lane="on")
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="srs")
        await sub.connect()
        await sub.subscribe("sr/#", qos=0)      # same filter as the rule
        pub = MqttClient(port=server.port, clientid="srp")
        await pub.connect()
        await pub.publish("sr/t", b"w", qos=0)  # slow path, earns permit
        await sub.recv(timeout=20)
        await _settle(0.5)
        for i in range(6):
            await pub.publish("sr/t", f"m{i}".encode(), qos=0)
            m = await sub.recv(timeout=20)
            assert m.payload == f"m{i}".encode()
            await asyncio.sleep(0.15)
        assert await _wait_fast(server, "lane_out", 1)
        # exactly once: no second copy of any payload is queued
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(timeout=0.5)
        assert await _wait_hits(hits, 7), len(hits)   # rule saw them all
        await sub.close(); await pub.close()

    run(main())
    server.stop()

"""Device router ON the live serving path (VERDICT r1 item 1): real MQTT
clients over TCP, deliveries coming off batched kernel launches, with
host-oracle fallback covered.  The reference equivalent is the whole of
emqx_broker.erl:218-232 driven from emqx_connection.erl:132."""

import asyncio

import pytest

from emqx_tpu.app import BrokerApp
from emqx_tpu.broker.server import BrokerServer
from emqx_tpu.config.config import Config
from emqx_tpu.mqtt import packet as P
from emqx_tpu.mqtt.client import MqttClient


def make_device_app(**kw):
    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.max_levels", 8)
    # this suite tests the KERNEL serving path: pin the latency knee to
    # 0 so even single-message batches launch the device (the adaptive
    # default would host-bypass them — covered by the policy tests)
    conf.put("router.device.min_batch", 0)
    return BrokerApp.from_config(conf, **kw)


@pytest.fixture
def run():
    def _run(scenario, app=None):
        async def main():
            server = BrokerServer(port=0, app=app or make_device_app())
            await server.start()
            try:
                await scenario(server)
            finally:
                await server.stop()
        asyncio.run(main())
    return _run


def test_from_config_builds_router_model():
    app = make_device_app()
    assert app.broker.model is not None
    assert app.pipeline is not None
    assert app.pipeline.max_batch == 512


def test_e2e_delivery_via_kernel(run):
    """Publishes from a live client must route through the device model
    (kernel-launch counter moves), not the host walk."""
    async def scenario(server):
        model = server.app.broker.model
        sub = MqttClient(port=server.port, clientid="sub")
        pub = MqttClient(port=server.port, clientid="pub")
        await sub.connect()
        await pub.connect()
        await sub.subscribe("room/+/temp", qos=1)
        launches0 = model.launch_count
        await pub.publish("room/7/temp", b"21.5", qos=1)
        # generous: the first publish pays the kernel's XLA compile
        got = await sub.recv(timeout=60)
        assert got.topic == "room/7/temp" and got.payload == b"21.5"
        assert model.launch_count > launches0
        assert server.app.pipeline.published >= 1
        await sub.disconnect()
        await pub.disconnect()
    run(scenario)


def test_e2e_concurrent_publishers_batched(run):
    """N clients publishing concurrently: every message delivered exactly
    once, and the pipeline coalesces (launches ≤ messages)."""
    async def scenario(server):
        model = server.app.broker.model
        sub = MqttClient(port=server.port, clientid="sub")
        await sub.connect()
        await sub.subscribe("fleet/#", qos=0)
        n_pubs, n_msgs = 8, 10
        pubs = [MqttClient(port=server.port, clientid=f"p{i}")
                for i in range(n_pubs)]
        for p in pubs:
            await p.connect()
        launches0 = model.launch_count

        async def blast(i, p):
            for j in range(n_msgs):
                await p.publish(f"fleet/v{i}/m{j}", b"x", qos=0)

        await asyncio.gather(*(blast(i, p) for i, p in enumerate(pubs)))
        want = {f"fleet/v{i}/m{j}"
                for i in range(n_pubs) for j in range(n_msgs)}
        got = set()
        while len(got) < len(want):
            m = await sub.recv(timeout=30)
            assert m.topic not in got, "duplicate delivery"
            got.add(m.topic)
        assert got == want
        launches = model.launch_count - launches0
        assert launches >= 1
        assert server.app.pipeline.published >= n_pubs * n_msgs
        for p in pubs:
            await p.disconnect()
        await sub.disconnect()
    run(scenario)


def test_e2e_ordering_per_publisher(run):
    """A publisher's messages arrive in submission order through the
    batched path (the per-connection ordering guarantee)."""
    async def scenario(server):
        sub = MqttClient(port=server.port, clientid="sub")
        pub = MqttClient(port=server.port, clientid="pub")
        await sub.connect()
        await pub.connect()
        await sub.subscribe("seq/t", qos=1)
        for i in range(20):
            await pub.publish("seq/t", b"%d" % i, qos=1)
        seen = [int((await sub.recv(timeout=30)).payload) for _ in range(20)]
        assert seen == list(range(20))
        await sub.disconnect()
        await pub.disconnect()
    run(scenario)


def test_e2e_host_oracle_fallback_deep_topic(run):
    """A topic deeper than router.device.max_levels overflows the kernel
    row and must take the host-oracle fallback — still delivered."""
    async def scenario(server):
        sub = MqttClient(port=server.port, clientid="sub")
        pub = MqttClient(port=server.port, clientid="pub")
        await sub.connect()
        await pub.connect()
        await sub.subscribe("deep/#", qos=0)
        deep = "deep/" + "/".join(str(i) for i in range(12))   # 13 levels
        await pub.publish(deep, b"fb", qos=0)
        got = await sub.recv(timeout=30)
        assert got.topic == deep and got.payload == b"fb"
        await sub.disconnect()
        await pub.disconnect()
    run(scenario)


def test_e2e_shared_and_retained_still_work(run):
    """Device path covers direct local subscribers; shared groups and
    retained messages ride their own seams — all must coexist."""
    async def scenario(server):
        a = MqttClient(port=server.port, clientid="a")
        b = MqttClient(port=server.port, clientid="b")
        pub = MqttClient(port=server.port, clientid="pub")
        await a.connect(); await b.connect(); await pub.connect()
        await a.subscribe("$share/g/t", qos=0)
        await b.subscribe("t", qos=0)
        await pub.publish("t", b"ret", qos=0, retain=True)
        got_b = await b.recv(timeout=30)
        assert got_b.payload == b"ret"
        got_a = await a.recv(timeout=30)
        assert got_a.payload == b"ret"
        # late subscriber gets the retained copy
        c = MqttClient(port=server.port, clientid="c")
        await c.connect()
        await c.subscribe("t", qos=0)
        got_c = await c.recv(timeout=30)
        assert got_c.payload == b"ret" and got_c.retain
        for cl in (a, b, pub, c):
            await cl.disconnect()
    run(scenario)


def test_small_batch_host_bypass_policy(run):
    """Latency policy (VERDICT r3 #3): batches below the knee answer
    from the host oracle (no device launch); a saturated batch still
    takes the kernel. Deliveries are correct on both legs.

    Deflaked (PR 4's documented timing flake) on BOTH wall-clock seams:
    the burst used to ride 16 separate writes, so under full-suite load
    the server could read them trickled into sub-knee batches; and the
    ADAPTIVE spill deadline (>= 30ms queue sojourn) could divert even a
    full batch to the host oracle on a loaded box. The burst is now ONE
    socket write (one read batch, one >= knee submission) and spill_ms
    is pinned far above any scheduler hiccup — the device launch is a
    policy decision again, not a race."""
    from emqx_tpu.mqtt.frame import serialize

    app = make_device_app()
    app.pipeline.min_device_batch = 4      # fixed knee for the test
    app.pipeline.spill_ms = 60_000.0       # no sojourn spill in-test

    async def scenario(server):
        model = app.broker.model
        sub = MqttClient(port=server.port, clientid="bp-s")
        await sub.connect()
        await sub.subscribe("kb/+", qos=0)
        pub = MqttClient(port=server.port, clientid="bp-p")
        await pub.connect()
        launches0 = model.launch_count
        # trickle: single-message batches stay on the host oracle (the
        # await-recv between publishes makes each its own batch)
        for i in range(3):
            await pub.publish("kb/t", f"lo{i}".encode(), qos=0)
            m = await sub.recv(timeout=10)
            assert m.payload == f"lo{i}".encode()
        assert app.pipeline.host_batches >= 3
        assert model.launch_count == launches0, "bypass launched kernel"
        # burst: one coalesced write of 16 frames lands as one read
        # batch well above the knee — the device path must run
        burst = b"".join(
            serialize(P.Publish(topic="kb/t", payload=f"hi{i}".encode(),
                                qos=0, properties={}),
                      pub.proto_ver)
            for i in range(16))
        pub._writer.write(burst)
        await pub._writer.drain()
        got = sorted([(await sub.recv(timeout=10)).payload
                      for _ in range(16)])
        assert got == sorted(f"hi{i}".encode() for i in range(16))
        assert model.launch_count > launches0, "burst did not use device"
        await sub.close(); await pub.close()

    run(scenario, app=app)


def test_host_bypass_rules_still_fire(run):
    """force_host batches must run rules through the normal hook fold
    (the co-batch gate stays off)."""
    app = make_device_app()
    app.pipeline.min_device_batch = 8
    hits = []
    app.rules.register_action("sink", lambda cols, a: hits.append(cols))
    app.rules.create_rule("r", 'SELECT topic FROM "rb/#"',
                          [{"function": "sink", "args": {}}])

    async def scenario(server):
        sub = MqttClient(port=server.port, clientid="rb-s")
        await sub.connect()
        await sub.subscribe("rb/t", qos=0)
        pub = MqttClient(port=server.port, clientid="rb-p")
        await pub.connect()
        for i in range(3):
            await pub.publish("rb/t", b"x", qos=0)
            await sub.recv(timeout=10)
        assert len(hits) == 3, hits
        await sub.close(); await pub.close()

    run(scenario, app=app)


def test_adaptive_knee_tracks_measured_costs():
    from emqx_tpu.broker.pipeline import PublishPipeline

    class FakeBroker:
        model = object()
    p = PublishPipeline(FakeBroker(), cm=None)
    p._rtt_ema = 0.070          # a 70 ms device round trip
    p._host_cost_ema = 5e-6     # measured oracle walk
    assert p.device_knee() == p.max_batch      # saturates at max_batch
    p._rtt_ema = 0.001          # local chip
    assert p.device_knee() == 200
    p.min_device_batch = 32     # explicit config wins
    assert p.device_knee() == 32
    p.broker.model = None
    assert p.device_knee() == 0


def test_pipeline_depth_preserves_order_and_raises_throughput(run):
    """VERDICT r4 #4: >2 in-flight launches. At depth 4 the per-
    publisher order still holds across a burst that spans many batches
    (collection is strictly in submission order)."""
    app = make_device_app()
    app.pipeline.depth = 4
    app.pipeline.max_batch = 8       # force many small batches

    async def scenario(server):
        sub = MqttClient(port=server.port, clientid="dsub")
        pub = MqttClient(port=server.port, clientid="dpub")
        await sub.connect()
        await pub.connect()
        await sub.subscribe("dp/t", qos=0)
        for i in range(120):
            await pub.publish("dp/t", b"%d" % i, qos=0)
        seen = [int((await sub.recv(timeout=30)).payload)
                for _ in range(120)]
        assert seen == list(range(120))
        assert app.pipeline.batches >= 120 // 8
        await sub.disconnect()
        await pub.disconnect()
    run(scenario, app=app)


def test_sojourn_spill_bounds_loaded_latency():
    """VERDICT r4 #4 spill: once a batch's head message has out-waited
    the deadline, the batch answers from the host oracle instead of
    joining the device queue — spilled_batches advances and delivery
    still happens."""
    import time as _t

    from emqx_tpu.core.message import Message

    app = make_device_app()
    app.broker.subscribe("s1", "sp/t")
    pipe = app.pipeline
    pipe.depth = 2
    pipe.spill_ms = 5            # tiny deadline: everything spills
    class _SpyCM:
        def __init__(self):
            self.got = []

        def dispatch(self, merged):
            self.got.append(merged)

    pipe.cm = _SpyCM()
    old = Message(topic="sp/t", payload=b"x")
    old.timestamp -= 1000        # aged 1s in the queue
    pipe.submit(old)
    pipe.flush()
    assert pipe.spilled_batches == 1, pipe.spilled_batches
    assert pipe.cm.got and "s1" in pipe.cm.got[0], pipe.cm.got

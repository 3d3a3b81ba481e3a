"""Device-lane segment timing: every lane message's path from socket read
to flush, cut into six segments on one clock (CLOCK_MONOTONIC ns).

The C++ host times read -> park (``latency.native.lane_park``) and
hand-off -> flush (``latency.native.lane_deliver``), plus the whole
read -> flush (``latency.native.lane_total``); the lane pump times park
-> submit -> submitted -> results on the host -> hand-off
(``latency.lane.wait|submit|device|decode``). The kind-4 lane record
carries the park stamp and the sampler's trace id to the pump."""

import asyncio
import socket
import struct
import time

import numpy as np
import pytest

from emqx_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native lib unavailable")

from emqx_tpu.broker.native_server import (LANE_SEGMENTS,  # noqa: E402
                                           NativeBrokerServer)
from emqx_tpu.mqtt.client import MqttClient  # noqa: E402
from emqx_tpu.observe.metrics import (LatencyHistogram,  # noqa: E402
                                      hist_bucket, hist_buckets)

SEGMENTS = (["latency.native.lane_park"]
            + [f"latency.lane.{s}" for s in LANE_SEGMENTS]
            + ["latency.native.lane_deliver"])
TOTAL = "latency.native.lane_total"


def _lane_app():
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.config import Config

    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.min_batch", 0)
    return BrokerApp.from_config(conf)


def _mqtt_connect(cid: bytes) -> bytes:
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    return bytes([0x10, len(vh)]) + vh


def _mqtt_publish(topic: bytes, payload: bytes) -> bytes:
    body = struct.pack(">H", len(topic)) + topic + payload
    return bytes([0x30, len(body)]) + body


def _raw_lane_record(tracing: bool, telemetry: bool = True):
    """One lane publish through a bare host: (record payload, the
    monotonic clock before the publish was sent, after it was polled)."""
    host = native.NativeHost(port=0, max_size=1 << 16)
    try:
        ids = []
        pub = socket.create_connection(("127.0.0.1", host.port))
        pub.sendall(_mqtt_connect(b"kp"))
        deadline = time.time() + 5
        framed = 0
        while (not ids or not framed) and time.time() < deadline:
            for kind, conn, _payload in host.poll(50):
                if kind == native.EV_OPEN:
                    ids.append(conn)
                elif kind == native.EV_FRAME:
                    framed += 1
        assert ids and framed, (ids, framed)
        host.set_telemetry(telemetry)
        host.set_tracing(tracing, 0)
        host.enable_fast(ids[0], 4, 64)
        host.permit(ids[0], "k/t")
        host.set_lane(True)
        list(host.poll(50))                    # apply the control ops
        before = time.monotonic_ns()
        pub.sendall(_mqtt_publish(b"k/t", b"x"))
        rec = None
        deadline = time.time() + 5
        while rec is None and time.time() < deadline:
            for kind, seq, payload in host.poll(50):
                if kind == native.EV_LANE:
                    rec = (seq, payload)
        after = time.monotonic_ns()
        assert rec is not None, "the publish did not park on the lane"
        pub.close()
        return rec, before, after
    finally:
        host.destroy()


def test_lane_record_round_trips_park_stamp_and_trace_id():
    (seq, payload), before, after = _raw_lane_record(tracing=True)
    park_ns, tid, topic = native.parse_lane(payload)
    assert topic == "k/t"
    assert seq >= 1
    # the park stamp is CLOCK_MONOTONIC, the clock time.monotonic_ns reads
    assert before <= park_ns <= after
    assert tid >> 63 == 1                      # a minted sampler id
    _rec, _b, _a = _raw_lane_record(tracing=False)
    park_ns, tid, topic = native.parse_lane(_rec[1])
    assert tid == 0 and topic == "k/t" and park_ns > 0


def test_lane_record_without_telemetry_has_no_stamps():
    (_seq, payload), _before, _after = _raw_lane_record(
        tracing=True, telemetry=False)
    assert native.parse_lane(payload) == (0, 0, "k/t")


def _drive(server, n, topic="ls/t"):
    """Warm a permit, then ``n`` lane publishes, each delivered; returns
    each histogram's (count, sum_ns) moves over the ``n``."""
    m = server.broker.metrics
    names = SEGMENTS + [TOTAL]

    async def main():
        sub = MqttClient(port=server.port, clientid="ls-sub")
        await sub.connect()
        await sub.subscribe(topic, qos=0)
        pub = MqttClient(port=server.port, clientid="ls-pub")
        await pub.connect()
        await pub.publish(topic, b"warm", qos=0)
        await sub.recv(timeout=20)
        await asyncio.sleep(0.8)             # the permit grant + its lane
        before = {k: (m.hist(k).count, m.hist(k).sum_ns) for k in names}
        lane0 = server.fast_stats()["lane_out"]
        for i in range(n):
            await pub.publish(topic, str(i).encode(), qos=0)
        got = [int((await sub.recv(timeout=20)).payload) for _ in range(n)]
        assert got == list(range(n))
        assert server.fast_stats()["lane_out"] - lane0 == n
        await asyncio.sleep(0.4)             # kind-8 deltas ship ~100 ms
        await sub.close()
        await pub.close()
        return {k: (m.hist(k).count - before[k][0],
                    m.hist(k).sum_ns - before[k][1]) for k in names}

    return asyncio.run(main())


def test_lane_segments_count_every_message_and_sum_to_total():
    server = NativeBrokerServer(port=0, app=_lane_app(), device_lane="on")
    server.start()
    try:
        n = 37
        moved = _drive(server, n)
    finally:
        server.stop()
    for name in SEGMENTS + [TOTAL]:
        assert moved[name][0] == n, (name, moved[name])
        assert moved[name][1] >= 0, (name, moved[name])
    # one clock, shared stamps: the six segments add up to the whole
    assert sum(moved[k][1] for k in SEGMENTS) == moved[TOTAL][1], moved
    assert moved[TOTAL][1] > 0


def test_lane_segments_telemetry_off_records_nothing():
    server = NativeBrokerServer(port=0, app=_lane_app(), device_lane="on",
                                telemetry=False)
    server.start()
    try:
        moved = _drive(server, 9)
        m = server.broker.metrics
        for name in SEGMENTS + [TOTAL]:
            assert moved[name] == (0, 0), (name, moved[name])
            assert m.hist(name).count == 0, name
        assert len(server.spans) == 0
    finally:
        server.stop()


def test_results_ready_stamp_is_per_collect():
    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.router.index import TrieIndex

    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    model.subscribe("a/b", 1)
    assert model.results_ready_ns() == 0
    h1 = model.publish_batch_submit(["a/b"])
    h2 = model.publish_batch_submit(["a/b"])
    t0 = time.monotonic_ns()
    model.publish_batch_collect(h1)
    t1 = model.results_ready_ns()
    assert t0 <= t1 <= time.monotonic_ns()
    model.publish_batch_collect(h2)
    assert model.results_ready_ns() >= t1


@pytest.mark.parametrize("values", [
    [0, 1, 2, 3, 1447, 1448, 1449, 2047, 2048, (1 << 32) - 1, 1 << 32,
     1 << 40, -5],
    list(range(0, 4096)),
])
def test_hist_buckets_match_the_scalar_bucket(values):
    got = hist_buckets(np.asarray(values, np.int64)).tolist()
    assert got == [hist_bucket(max(v, 0)) for v in values]


def test_weighted_and_vectorized_observe_match_one_by_one():
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1 << 34, 500)
    one, many, weighted = (LatencyHistogram() for _ in range(3))
    for v in vals.tolist():
        one.observe(v)
    many.observe_many(vals)
    assert one.count == many.count == 500
    assert one.sum_ns == many.sum_ns
    assert one.counts.tolist() == many.counts.tolist()
    weighted.observe_n(12_345, 17)
    ref = LatencyHistogram()
    for _ in range(17):
        ref.observe(12_345)
    assert (weighted.count, weighted.sum_ns) == (ref.count, ref.sum_ns)
    assert weighted.counts.tolist() == ref.counts.tolist()

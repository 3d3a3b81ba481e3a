"""Kernel-plane observability (ISSUE 19): in-kernel per-batch counters
ride the existing collect device_get, the DeviceMetricsFold turns them
plus the submit/step/decode wall timings into histograms, trie-health
gauges and ledger reasons — the same surfaces every other plane exports
through. A sampled lane message's timeline gains the kernel's
submit/collect points from the lane pump, under the message's own id."""

import asyncio

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.core.message import Message
from emqx_tpu.models.router_model import RouterModel
from emqx_tpu.observe.device_metrics import (KERNEL_COUNTER_FIELDS,
                                             DeviceMetricsFold)
from emqx_tpu.observe.metrics import Metrics, DegradationLedger
from emqx_tpu.router.index import ShardedTrieIndex, TrieIndex

# a full exact/plus fan: the frontier doubles every level, so K=2
# overflows on the 4-deep topic (differentially verified in
# test_kernel_counters_lint)
FAN_FILTERS = ["a/b/c/d", "a/b/c/+", "a/b/+/d", "a/b/+/+",
               "a/+/c/d", "a/+/c/+", "a/+/+/d", "a/+/+/+"]


def _fold(model):
    metrics = Metrics()
    ledger = DegradationLedger(metrics)
    fold = DeviceMetricsFold(metrics, ledger=ledger, model=model)
    model.telemetry = fold
    return metrics, ledger, fold


def _drive(model, topics):
    return model.publish_batch_collect(model.publish_batch_submit(topics))


# -- fold math ---------------------------------------------------------------


def test_fold_counters_and_stage_hists_flat():
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    for f in ("a/+/c", "a/b/#", "d/e"):
        model.subscribe(f, 1)
    metrics, _ledger, fold = _fold(model)

    _drive(model, ["a/b/c", "d/e", "x/y"])
    assert fold.batches == 1
    assert fold.last is not None and fold.last.n_shards == 1
    last = fold.last.as_dict()
    assert last["cand_pre"] == 3          # a/+/c + a/b/# + d/e
    assert last["cand_post"] == 3
    assert last["overflow_rows"] == 0 and last["trunc_rows"] == 0
    assert last["frontier_peak"] >= 2 and last["probe_iters"] >= 1
    # every stage histogram observed exactly one batch
    for h in fold.stage_hists().values():
        assert h.count == 1
    _drive(model, ["a/b/c"])
    assert fold.batches == 2
    assert fold.totals["cand_pre"] == 3 + 2   # sums across batches

    # walk_levels sums over batches: 4 levels twice, then 3
    _drive(model, ["d/e"])
    assert fold.last.value("walk_levels") == 3
    assert fold.totals["walk_levels"] == 4 + 4 + 3

    ks = fold.kernel_summary()
    assert ks["batches"] == 3
    assert set(ks["counters"]) == set(KERNEL_COUNTER_FIELDS)
    assert ks["stages"]["step"]["count"] == 3


def test_fold_truncation_counter():
    # M=1 candidate cap: a topic matching 2 filters truncates its row
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, M=1)
    model.subscribe("t/+", 1)
    model.subscribe("t/1", 2)
    _metrics, _ledger, fold = _fold(model)
    _drive(model, ["t/1"])
    last = fold.last.as_dict()
    assert last["trunc_rows"] >= 1
    assert last["cand_post"] <= last["cand_pre"]


def test_fold_gauges_flat_and_upload_slots():
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    for f in ("a/b", "c/+", "d/#"):
        model.subscribe(f, 1)
    metrics, _ledger, fold = _fold(model)
    model.refresh()
    g = fold.gauges()
    assert g["filters_total"] == 3 and g["shards"] == 1
    assert g["shard_skew"] == 1.0
    assert 0 < g["node_occupancy"] < 1 and 0 < g["edge_load"] < 1
    assert g["uploads"] >= 1
    # the promoted fixed slots sync from the model's ad-hoc counters
    assert metrics.val("kernel.uploads") == model.upload_count >= 1
    # an incremental subscribe after the first upload scatter-patches:
    # patch slot + unpadded byte gauge move
    model.subscribe("new/filter/x", 3)
    model.refresh()
    g = fold.gauges()
    assert g["upload_patches"] == model.patch_count >= 1
    assert g["patch_upload_bytes"] > 0
    assert metrics.val("kernel.upload_patches") >= 1


def test_fold_gauges_sharded_skew():
    idx = ShardedTrieIndex(4, max_levels=8)
    model = RouterModel(idx, n_sub_slots=64)
    # all filters hash wherever they hash; skew = max/mean over the
    # per-shard live-filter counts, computed from the index itself
    for i in range(16):
        model.subscribe(f"s/{i}/x", 1)
    _metrics, _ledger, fold = _fold(model)
    g = fold.gauges()
    assert g["shards"] == 4
    assert isinstance(g["filters"], list) and len(g["filters"]) == 4
    assert sum(g["filters"]) == g["filters_total"] == 16
    counts = [c for c in g["filters"] if c > 0]
    assert g["shard_skew"] == pytest.approx(max(g["filters"]) / 4.0)
    assert isinstance(g["node_occupancy"], list)
    # per-shard counters decode as [S, C]
    _drive(model, ["s/3/x", "s/7/x"])
    assert fold.last.n_shards == 4
    assert int(fold.last.field("cand_pre").sum()) == 2
    # every shard walks the same 4 levels; the fold sums the shards
    assert fold.last.field("walk_levels").tolist() == [4] * 4
    assert fold.totals["walk_levels"] == 16


# -- trace stitching ---------------------------------------------------------


def _lane_timelines(shift, n):
    """``n`` lane publishes through a CPU NativeBrokerServer with the
    device lane on, every publish in a poll cycle of its own, sampled
    1-in-2^``shift``; returns (the sampled timelines, traced_pubs)."""
    from emqx_tpu import native
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.config.config import Config
    from emqx_tpu.mqtt.client import MqttClient

    if not native.available():
        pytest.skip("native lib unavailable")
    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.min_batch", 0)
    server = NativeBrokerServer(port=0, app=BrokerApp.from_config(conf),
                                device_lane="on", trace_sample_shift=shift)
    server.start()

    async def main():
        sub = MqttClient(port=server.port, clientid="ks")
        await sub.connect()
        await sub.subscribe("kt/a", qos=0)
        pub = MqttClient(port=server.port, clientid="kp")
        await pub.connect()
        await pub.publish("kt/a", b"warm", qos=0)    # earns the permit
        await sub.recv(timeout=20)
        await asyncio.sleep(0.8)
        lane0 = server.fast_stats()["lane_out"]
        for i in range(n):
            await pub.publish("kt/a", str(i).encode(), qos=0)
            assert (await sub.recv(timeout=20)).payload == str(i).encode()
        assert server.fast_stats()["lane_out"] - lane0 == n
        await asyncio.sleep(0.3)                     # kind-12 spans land
        await sub.close()
        await pub.close()

    try:
        asyncio.run(main())
        return server.spans_recent(64), server.fast_stats()["traced_pubs"]
    finally:
        server.stop()


def _in_order(stages, want):
    it = iter(stages)
    return all(w in it for w in want)


def test_span_stitching_submit_collect():
    timelines, traced = _lane_timelines(0, 1)
    assert traced == 1 and len(timelines) == 1
    spans = timelines[0]["spans"]
    stages = [s["stage"] for s in spans]
    # the timeline starts at the socket read, and the kernel's points
    # sit between it and the route, under the message's own trace id
    assert stages[0] == "ingress", stages
    assert _in_order(stages, ["ingress", "kernel_submit",
                              "kernel_collect", "route"]), stages
    t = {s["stage"]: s["t_ns"] for s in spans}
    assert t["ingress"] <= t["kernel_submit"] <= t["kernel_collect"] \
        <= t["route"]
    assert {s["aux"] for s in spans
            if s["stage"].startswith("kernel_")} == {1}   # batch size


def test_span_sampling_1_in_n():
    timelines, traced = _lane_timelines(2, 8)
    # the sampler ticks once per lane park: parks 4 and 8 of 8
    assert traced == 2 and len(timelines) == 2
    for tl in timelines:
        stages = [s["stage"] for s in tl["spans"]]
        assert stages[0] == "ingress", stages
        assert _in_order(stages, ["ingress", "kernel_submit",
                                  "kernel_collect", "route"]), stages


# -- broker fallback seam ----------------------------------------------------


def test_broker_kernel_overflow_ledger():
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64, K=2)
    metrics = Metrics()
    b = Broker(router_model=model, metrics=metrics)
    b.ledger = DegradationLedger(metrics)
    for i, f in enumerate(FAN_FILTERS):
        b.subscribe(f"c{i}", f)
    out = b.publish_batch([Message(topic="a/b/c/d"),
                           Message(topic="a/b/c/x")])
    # the K=2 frontier punts both rows to the host oracle — delivery
    # still complete...
    assert len(out[0]) == len(FAN_FILTERS)
    # ...and the degradation is on the ledger with its row count
    assert b.ledger.totals()["kernel_overflow"] == 2
    ev = [e for e in b.ledger.recent(8)
          if e["reason"] == "kernel_overflow"]
    assert ev and ev[-1]["count"] == 2
    assert metrics.val("messages.ledger.kernel_overflow") == 2
    assert metrics.val("messages.ledger.kernel_hostmatch") == 0


def test_broker_kernel_hostmatch_ledger(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_CPU_KERNEL", "host")
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    assert model._host_matcher is not None
    metrics = Metrics()
    b = Broker(router_model=model, metrics=metrics)
    b.ledger = DegradationLedger(metrics)
    b.subscribe("c1", "a/b")
    out = b.publish_batch([Message(topic="a/b")])
    assert "c1" in out[0]
    assert metrics.val("messages.kernel.hostmatch") == 1
    assert b.ledger.totals()["kernel_hostmatch"] == 1
    assert metrics.val("messages.ledger.kernel_hostmatch") == 1
    assert b.ledger.totals().get("kernel_overflow", 0) == 0


# -- escape hatch ------------------------------------------------------------


def test_kernel_telemetry_escape_hatch(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_KERNEL_TELEMETRY", "0")
    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    assert model.kernel_telemetry is False
    model.subscribe("a/b", 1)
    _metrics, _ledger, fold = _fold(model)
    _drive(model, ["a/b"])
    # stage timings still fold (host-side clocks cost nothing); the
    # device counters are compiled out
    assert fold.batches == 1
    assert fold.last is None
    monkeypatch.setenv("EMQX_TPU_KERNEL_TELEMETRY", "1")
    assert RouterModel(TrieIndex()).kernel_telemetry is True
    # explicit ctor flag beats the env
    monkeypatch.setenv("EMQX_TPU_KERNEL_TELEMETRY", "0")
    assert RouterModel(TrieIndex(),
                       kernel_telemetry=True).kernel_telemetry is True


# -- app wiring: prometheus, $SYS, mgmt, server surface ----------------------


def _app():
    from emqx_tpu.app import BrokerApp

    model = RouterModel(TrieIndex(max_levels=8), n_sub_slots=64)
    return BrokerApp(router_model=model)


def test_app_wires_fold_and_prometheus_gauges():
    app = _app()
    assert app.device_metrics is not None
    assert app.broker.model.telemetry is app.device_metrics
    # spans come from a native server's collector alone, none booted yet
    assert app.native_spans_fn is None
    app.broker.subscribe("c1", "a/b")
    app.broker.publish_batch([Message(topic="a/b")])
    out = app.prometheus()
    assert "emqx_kernel_filters_total" in out
    assert "emqx_kernel_shard_skew" in out
    assert "emqx_kernel_batches" in out
    assert "emqx_latency_kernel_submit_seconds_count" in out
    assert "emqx_latency_kernel_decode_seconds_count" in out
    ks = app.kernel_summary()
    assert ks["batches"] == 1 and "gauges" in ks


def test_app_without_kernel_telemetry(monkeypatch):
    monkeypatch.setenv("EMQX_TPU_KERNEL_TELEMETRY", "off")
    app = _app()
    assert app.device_metrics is None
    assert app.kernel_summary() == {}
    assert "emqx_kernel_batches" not in app.prometheus()


def test_sys_kernel_heartbeat_renders_at_zero():
    from emqx_tpu.observe.sys import SysHeartbeat

    app = _app()
    seen = {}
    hb = SysHeartbeat("n1", lambda m: seen.__setitem__(
        m.topic, m.payload), metrics=app.metrics,
        kernel=app.device_metrics)
    hb.publish_kernel()
    for stage in ("submit", "step", "decode"):
        assert seen[f"$SYS/brokers/n1/kernel/{stage}/p50"] == b"0.000"
        assert seen[f"$SYS/brokers/n1/kernel/{stage}/p99"] == b"0.000"
        assert seen[f"$SYS/brokers/n1/kernel/{stage}/count"] == b"0"
    # and it rides the slow tick next to metrics/latency/ledger
    hb.tick(now=1e12)
    assert "$SYS/brokers/n1/kernel/step/p99" in seen


def test_mgmt_kernel_stats_endpoint():
    from emqx_tpu.mgmt.api import ApiError, ManagementApi

    app = _app()
    app.broker.subscribe("c1", "a/b")
    app.broker.publish_batch([Message(topic="a/b")])
    api = ManagementApi(app)
    snap = api.h_kernel_stats({}, None)
    assert snap["gauges"]["filters_total"] == 1
    assert snap["summary"]["batches"] == 1
    assert snap["last_per_shard"]["cand_pre"] == [1]
    status, body = api.handle("GET", "/api/v5/kernel/stats", {}, None,
                              authed=True)
    assert status == 200 and body["summary"]["batches"] == 1

    app.device_metrics = None
    with pytest.raises(ApiError) as ei:
        api.h_kernel_stats({}, None)
    assert ei.value.status == 404


def test_server_kernel_summary_surface():
    from emqx_tpu.broker.server import BrokerServer

    app = _app()
    srv = BrokerServer(app=app, port=0)
    app.broker.subscribe("c1", "a/b")
    app.broker.publish_batch([Message(topic="a/b")])
    ks = srv.kernel_summary()
    assert ks["batches"] == 1
    assert ks["stages"]["submit"]["count"] == 1

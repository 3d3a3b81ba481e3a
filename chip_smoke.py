"""Chip smoke: the served path once, end to end, on a TPU.

    python chip_smoke.py             # one chip: NativeBrokerServer at 1M filters
    python chip_smoke.py --chips 4   # only the sharded trie over a 2x2 host

One chip (BASELINE config 2): ``BrokerApp.from_config`` with the device
router on, 1,000,000 connected-vehicle filters resident in the device
trie, ``NativeBrokerServer`` with the device lane on, ~200 socket
subscribers and ~2,000 socket publishes in bursts of several sizes, one
subscribe and one unsubscribe while bursts are in flight. Every
subscriber's delivered set must equal the host oracle
(``router/trie.py``) over the subscribed filters, and the lane must have
served with no punt, stale trip, soft-cap walk, failover or host match.

Four chips (BASELINE config 3): ``RouterModel(ShardedTrieIndex(4),
mesh=make_mesh(4))`` over 10M filters on a (dp=1, tp=4) mesh; its
``publish_batch`` must equal the host oracle and a one-chip replicated
``RouterModel`` over the same filters.

Without a TPU it exits non-zero and prints no result. Any failure ends
the run non-zero. The last stdout line is the contract:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time

N_FILTERS = 1_000_000          # BASELINE config 2
N_FILTERS_SHARDED = 10_000_000  # BASELINE config 3
N_SUBSCRIBERS = 200
N_PUBLISHERS = 4
BURSTS = (40, 300, 900, 160, 600)   # lane batches land in several buckets


def log(*a) -> None:
    print(*a, flush=True)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU "
                         f"(platform={devices[0].platform}); no result")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: need {n_chips} chips, "
                         f"JAX found {len(devices)}")
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    return devices[:n_chips]


def device_bytes(devices) -> list[int]:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# -- one chip: the served path -------------------------------------------------

def served(seed: int, devices) -> None:
    import jax
    import numpy as np

    from emqx_tpu import native
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.config.config import Config
    from emqx_tpu.router.fleet import build_filters

    t0 = time.perf_counter()
    check(native.available(), f"native build: {native.build_error()}")
    log(f"native library ready in {time.perf_counter() - t0:.3f}s")

    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.min_batch", 0)
    app = BrokerApp.from_config(conf)
    model = app.broker.model
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    filters = build_filters(N_FILTERS, rng)
    # the fleet's standing subscriptions, held by the device trie; the
    # socket subscribers below join filters drawn from the same set
    for i, f in enumerate(filters):
        model.subscribe(f, i % model.n_sub_slots)
    t1 = time.perf_counter()
    model.refresh()
    jax.block_until_ready(model._trie_dev)
    t2 = time.perf_counter()
    arrays = model.index.arrays
    n_live = sum(f is not None for f in model.index.filters)
    log(f"filters: {len(filters)} generated+subscribed in {t1 - t0:.3f}s, "
        f"{n_live} distinct")
    log(f"trie: host build + upload {t2 - t1:.3f}s, nodes={arrays.n_nodes} "
        f"edge_table={arrays.ht_parent.shape[0]}")
    log(f"device bytes_in_use after upload: {device_bytes(devices)[0]}")

    server = NativeBrokerServer(port=0, app=app, device_lane="on")
    t0 = time.perf_counter()
    server.start()
    check(server.lane_open.is_set(), "device lane did not open")
    compile_s = server.lane_compile_s
    log(f"lane open after {time.perf_counter() - t0:.3f}s; compiled "
        f"{len(compile_s)} programs in {sum(compile_s.values()):.3f}s")
    for name, s in compile_s.items():
        log(f"  compile {name}: {s:.3f}s")
    try:
        asyncio.run(_drive(server, app, filters, rng))
        st = server.fast_stats()
    finally:
        server.stop()
    m = app.metrics
    log(f"lane: in={st['lane_in']} out={st['lane_out']} "
        f"punts={st['lane_punts']} stale={st['lane_stale']} "
        f"fallback={st['lane_fallback']} launches={model.launch_count} "
        f"patches={model.patch_count} uploads={model.upload_count}")
    log(f"messages.device_failover={m.val('messages.device_failover')} "
        f"messages.kernel.hostmatch={m.val('messages.kernel.hostmatch')}")
    stats = devices[0].memory_stats()
    log(f"device bytes_in_use={stats['bytes_in_use']} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    check(st["lane_in"] > 0, "no publish rode the device lane")
    check(st["lane_out"] == st["lane_in"], "lane_out != lane_in")
    for key in ("lane_punts", "lane_stale", "lane_fallback"):
        check(st[key] == 0, f"{key}={st[key]}")
    for key in ("messages.device_failover", "messages.kernel.hostmatch"):
        check(m.val(key) == 0, f"{key}={m.val(key)}")


async def _drive(server, app, filters, rng) -> None:
    from emqx_tpu.core import topic as T
    from emqx_tpu.mqtt.client import MqttClient
    from emqx_tpu.router.fleet import make_topics
    from emqx_tpu.router.trie import Trie

    port = server.port
    n_vehicles = N_FILTERS // 2
    # distinct subscriber filters, half of them wildcards
    wild = [f for f in filters[:200_000] if "+" in f or "#" in f]
    exact = [f for f in filters[:200_000] if "+" not in f and "#" not in f]
    half = N_SUBSCRIBERS // 2
    sub_filters = [str(f) for f in (
        list(dict.fromkeys(rng.choice(wild, 4 * half)))[:half]
        + list(dict.fromkeys(rng.choice(exact, 4 * half)))[:half])]
    # the publish pool: instances of subscribed filters plus topics only
    # the standing (socket-less) subscriptions match
    pool = list(dict.fromkeys(make_topics(sub_filters, rng, 160, n_vehicles)
                              + make_topics(filters, rng, 40, n_vehicles)))
    # one filter no one holds yet, subscribed and unsubscribed mid-run;
    # only the reserved topics match it
    fleet, part = pool[0].split("/")[1], pool[0].split("/")[5]
    churn_filter = f"fleet/{fleet}/vehicle/+/part/{part}/#"
    check(app.broker.model.index.fid_of(churn_filter) is None,
          "churn filter already in the trie")
    reserved = [f"fleet/{fleet}/vehicle/v{v}/part/{part}/m{m}"
                for v, m in zip(rng.integers(0, n_vehicles, 8),
                                rng.integers(0, 16, 8))]
    common = [t for t in pool if not T.match(t, churn_filter)]
    topics = common + reserved
    owner = {t: i % N_PUBLISHERS for i, t in enumerate(topics)}

    subs = [MqttClient(port=port, clientid=f"smoke-sub-{i}")
            for i in range(N_SUBSCRIBERS)]
    pubs = [MqttClient(port=port, clientid=f"smoke-pub-{i}")
            for i in range(N_PUBLISHERS)]
    churn = MqttClient(port=port, clientid="smoke-churn")
    clients = subs + pubs + [churn]
    await asyncio.gather(*(c.connect() for c in clients))
    await asyncio.gather(*(s.subscribe(f) for s, f in zip(subs, sub_filters)))
    log(f"clients: {len(subs)} subscribers "
        f"({sum('+' in f or '#' in f for f in sub_filters)} wildcard), "
        f"{len(pubs)} publishers, {len(topics)} topics")

    oracle = Trie()
    for f in sub_filters + [churn_filter]:
        oracle.insert(f)
    sub_of = {f: i for i, f in enumerate(sub_filters)}
    expected = [set() for _ in subs]
    churn_expected: set = set()
    received = [[] for _ in subs]
    churn_received: list = []
    sent: list = []          # (id, topic)
    churn_on = False

    async def burst(batch) -> None:
        per = [[] for _ in pubs]
        for t in batch:
            mid = len(sent)
            sent.append((mid, t))
            for f in oracle.match(t):
                if f == churn_filter:
                    if churn_on:
                        churn_expected.add(mid)
                else:
                    expected[sub_of[f]].add(mid)
            per[owner[t]].append((t, str(mid).encode()))

        async def send(pub, msgs):
            for t, payload in msgs:
                await pub.publish(t, payload, qos=0)
        await asyncio.gather(*(send(p, ms) for p, ms in zip(pubs, per)))

    async def settle(timeout: float = 60.0) -> None:
        """Wait until every subscriber holds its expected count, then
        a quiet spell so a duplicate or stray delivery shows."""
        def drain():
            for q, out in zip(subs, received):
                while not q.messages.empty():
                    out.append(int(q.messages.get_nowait().payload))
            while not churn.messages.empty():
                churn_received.append(int(churn.messages.get_nowait().payload))
        deadline = time.monotonic() + timeout
        while True:
            drain()
            short = [(sub_filters[i], sorted(e - set(r))[:5])
                     for i, (r, e) in enumerate(zip(received, expected))
                     if len(r) < len(e)]
            if len(churn_received) < len(churn_expected):
                short.append((churn_filter, len(churn_expected)))
            if not short:
                break
            check(time.monotonic() < deadline,
                  f"deliveries missing after {timeout}s: {short[:5]}")
            await asyncio.sleep(0.02)
        await asyncio.sleep(0.5)
        drain()

    # permit warm-up: the first publish per (conn, topic) takes the
    # Python path; later ones are permitted onto the C++ fast path
    t0 = time.perf_counter()
    await burst(topics)
    await settle()
    await asyncio.sleep(1.0)
    log(f"permit warm-up: {len(topics)} slow-path publishes delivered in "
        f"{time.perf_counter() - t0:.3f}s")

    # first publish→deliver on the lane
    probe = next(t for t in common if oracle.match(t))
    target = sub_of[oracle.match(probe)[0]]
    lane_in0 = server.fast_stats()["lane_in"]
    t0 = time.perf_counter()
    await burst([probe])
    received[target].append(int((await subs[target].messages.get()).payload))
    log(f"first lane publish->deliver: {(time.perf_counter() - t0) * 1e3:.3f}"
        f" ms (lane_in +{server.fast_stats()['lane_in'] - lane_in0})")
    await settle()

    for k, n in enumerate(BURSTS):
        mix = topics if k in (2, 4) else common
        batch = [mix[i] for i in rng.integers(0, len(mix), n)]
        t0 = time.perf_counter()
        if k == 1:      # subscribe while this burst's batches are in flight
            await asyncio.gather(burst(batch), churn.subscribe(churn_filter))
            churn_on = True
        elif k == 3:    # and unsubscribe while the next one's are
            churn_on = False
            await asyncio.gather(burst(batch),
                                 churn.unsubscribe(churn_filter))
        else:
            await burst(batch)
        await settle()
        log(f"burst {k}: {n} publishes settled in "
            f"{time.perf_counter() - t0:.3f}s")

    await asyncio.gather(*(c.close() for c in clients))

    n_deliveries = 0
    for i, (got, want) in enumerate(zip(received, expected)):
        check(len(got) == len(set(got)),
              f"duplicate delivery to {sub_filters[i]}")
        check(set(got) == want,
              f"{sub_filters[i]}: missing {sorted(want - set(got))[:5]} "
              f"extra {sorted(set(got) - want)[:5]}")
        n_deliveries += len(got)
    check(set(churn_received) == churn_expected
          and len(churn_received) == len(churn_expected),
          f"churn subscriber: got {sorted(churn_received)[:10]} "
          f"want {sorted(churn_expected)[:10]}")
    check(len(churn_expected) > 0, "churn subscriber was never exercised")
    log(f"deliveries: {n_deliveries + len(churn_received)} over {len(sent)} "
        f"publishes equal the host oracle for {len(subs) + 1} subscribers "
        f"(churn subscriber: {len(churn_received)})")


# -- four chips: the sharded trie ---------------------------------------------

def sharded(seed: int, devices) -> None:
    import jax
    import numpy as np

    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.router.fleet import build_filters, make_topics
    from emqx_tpu.router.index import ShardedTrieIndex, TrieIndex
    from emqx_tpu.router.trie import Trie

    # tens of millions of long-lived objects: cyclic GC passes over them
    # would only slow the host-side build
    gc.disable()
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    filters = build_filters(N_FILTERS_SHARDED, rng)
    log(f"filters: {len(filters)} generated in {time.perf_counter() - t0:.3f}s")

    def load(model, label):
        t0 = time.perf_counter()
        for i, f in enumerate(filters):
            model.subscribe(f, i % model.n_sub_slots)
        t1 = time.perf_counter()
        model.refresh()
        jax.block_until_ready(model._trie_dev)
        log(f"{label}: subscribed in {t1 - t0:.3f}s, host build + upload "
            f"{time.perf_counter() - t1:.3f}s")

    mesh = make_mesh(len(devices), shape=(1, len(devices)),
                     devices=devices)
    sh = RouterModel(ShardedTrieIndex(len(devices)), mesh=mesh)
    load(sh, f"sharded S={len(devices)} mesh=(dp=1, tp={len(devices)})")
    per_dev = device_bytes(devices)
    log(f"bytes_in_use per device (sharded trie only): {per_dev}")
    n_live = sum(f is not None for f in sh.index.filters)
    log(f"distinct filters: {n_live}")

    topics = make_topics(filters, rng, 4096, N_FILTERS_SHARDED // 2)
    t0 = time.perf_counter()
    got_sh = sh.publish_batch(topics)
    log(f"sharded publish_batch({len(topics)}): "
        f"{time.perf_counter() - t0:.3f}s (compile included)")
    t0 = time.perf_counter()
    sh.publish_batch(topics)
    log(f"sharded publish_batch warm: {time.perf_counter() - t0:.3f}s")

    rep = RouterModel(TrieIndex())
    load(rep, "replicated, one chip")
    log(f"bytes_in_use per device (+ replicated trie): "
        f"{device_bytes(devices)}")
    got_rep = rep.publish_batch(topics)

    # the oracle holds every filter that can match a sampled topic: a
    # literal 4th word (the vehicle) must equal the topic's, so filters
    # naming another vehicle cannot match and are left out
    t0 = time.perf_counter()
    vehicles = {t.split("/")[3] for t in topics}
    oracle = Trie()
    for f in dict.fromkeys(filters):
        w = f.split("/", 4)
        if len(w) < 4 or "#" in w[:4] or w[3] == "+" or w[3] in vehicles:
            oracle.insert(f)
    log(f"host oracle: {len(oracle)} candidate filters in "
        f"{time.perf_counter() - t0:.3f}s")

    matched_sh, _, _, fb_sh = got_sh
    matched_rep, _, _, fb_rep = got_rep
    check(fb_sh == fb_rep, f"fallback rows differ: {fb_sh[:5]} {fb_rep[:5]}")
    fb = set(fb_sh)
    checked = n_matches = 0
    for i, t in enumerate(topics):
        want = set(oracle.match(t))
        if i in fb:
            continue
        check(set(matched_sh[i]) == want,
              f"sharded != oracle on {t}: {sorted(matched_sh[i])} {sorted(want)}")
        check(set(matched_rep[i]) == want,
              f"replicated != oracle on {t}: {sorted(matched_rep[i])}")
        checked += 1
        n_matches += len(want)
    check(checked > 0 and len(fb) <= len(topics) // 100,
          f"{len(fb)} of {len(topics)} topics fell back")
    check(max(per_dev) < 2 * min(per_dev),
          f"the shards are not spread over the chips: {per_dev}")
    log(f"sharded == replicated == oracle on {checked}/{len(topics)} topics "
        f"({n_matches} matches, {len(fb)} fallback rows)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from emqx_tpu.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = require_tpu(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded(args.seed, devices)
    else:
        served(args.seed, devices)
    log(f"phase done in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    sys.exit(main())

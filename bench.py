"""Route-match throughput benchmark (the BASELINE.json north-star metric).

Measures the flagship device step — batched wildcard match + compact +
subscriber-shard fan-out — against a connected-vehicle-style filter set
(BASELINE configs 2/3: ~1M subscriptions, ~10% single-level '+' wildcards,
7-level topic tree). The reference equivalent is `emqx_router:match_routes/1`
(per-message Erlang trie walk over ETS, apps/emqx/src/emqx_router.erl:141-153,
driven in-VM by apps/emqx/src/emqx_broker_bench.erl).

Prints a cumulative JSON line after EVERY completed section (the last line
is the full artifact):
  {"metric": "route-matches/sec", "value": N, "unit": "topics/sec",
   "vs_baseline": X, ...}

Supervision model:
  * the supervisor never touches JAX; one probe child checks for a TPU
    and the bench exits non-zero without one — there is no CPU plan;
  * each section runs as its OWN child process with its OWN deadline (and
    so owns the chip alone), so a failure in section k cannot take
    sections 1..k-1 (or the host-CPU sections) down with it;
  * sections write partial results to $BENCH_PARTIAL_DIR/section_<name>.json
    as they go, and the supervisor re-emits the cumulative stdout line after
    every section — a SIGKILL at any point leaves the newest cumulative
    line in the tail;
  * section children keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says, else in <checkout>/.jax_cache.

vs_baseline: ratio against the reference's own headline sustained cluster
throughput of 1M msg/s (reference README.md:16) — every routed message
needs exactly one match_routes call, so topics-matched/sec is directly
comparable. No per-config BEAM numbers are published (BASELINE.md).

Latency is measured with synchronous dispatch (block every step);
throughput with the production discipline — a bounded in-flight window of
batches (SURVEY.md §2.5-6 pipeline parallelism: batch assembly overlaps
device execution, as the reference overlaps socket reads with dispatch via
{active,N}) — every output is still blocked on before it leaves the window.

Env knobs: BENCH_FILTERS (default 1_000_000), BENCH_BATCH (16384),
BENCH_ITERS (100), BENCH_SHARDS (8192 subscriber fan-out shards),
BENCH_WINDOW (8 in-flight batches), BENCH_LAT_ITERS (30 sync latency
samples), BENCH_TOTAL_BUDGET_S (3300), BENCH_SECTION (internal: run one
section inline), BENCH_PARTIAL_DIR (internal: partial-results directory).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

from emqx_tpu.router.fleet import build_filters, make_topics


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# partial-results plumbing
# ---------------------------------------------------------------------------

RESULTS: dict = {}


def flush_results(section: str) -> None:
    """Atomically persist this section's results-so-far. Called after every
    subsection so a mid-section wedge still lands the completed numbers."""
    d = os.environ.get("BENCH_PARTIAL_DIR")
    if not d:
        return
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{section}.tmp")
    with open(tmp, "w") as f:
        json.dump(RESULTS, f)
    os.replace(tmp, os.path.join(d, f"section_{section}.json"))


def put_broker_hists(section: str, server, prefix: str) -> dict:
    """Persist broker-SIDE stage latency percentiles (the native
    telemetry plane's histograms, native_server.latency_summary) next
    to the loadgen-side numbers — p50/p99/p999 per stage in µs. The
    loadgen measures publish→deliver across the wire; these split that
    budget into the in-broker stages (ingress→route, route→flush, ack
    RTTs, lane dwell, GIL stints), so ROADMAP's 'p99 <= 2ms' gate can
    be audited from the broker's own clocks, not just the client's."""
    # hist deltas ship on a ~100ms cadence (host.cc): give the poll
    # loop a few idle cycles so the run's FINAL window (incl. the tail
    # ack-RTT samples) reaches the Python accumulators before we read
    time.sleep(0.5)
    try:
        summ = server.latency_summary()
    except Exception:  # noqa: BLE001 — telemetry off / old server
        return {}
    kv = {}
    for stage, s in summ.items():
        kv[f"{prefix}_{stage}_p50_us"] = s["p50_us"]
        kv[f"{prefix}_{stage}_p99_us"] = s["p99_us"]
        kv[f"{prefix}_{stage}_p999_us"] = s["p999_us"]
        kv[f"{prefix}_{stage}_count"] = s["count"]
    if kv:
        put(section, **kv)
    return summ


def _require_tpu() -> str:
    """Device sections measure the chip or nothing: off a TPU they
    fail instead of writing CPU numbers under device metric names."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"device section needs a TPU, found {platform}")
    return platform


def put(section: str, **kv) -> None:
    RESULTS.update(kv)
    flush_results(section)


# ---------------------------------------------------------------------------
# shared builders (BASELINE config 2/3 shape)
# ---------------------------------------------------------------------------

def build_model(n_filters: int, rng: np.random.Generator, n_shards: int,
                mesh=None, trie_shards: Optional[int] = None):
    """Index + RouterModel with one subscriber shard per subscription,
    uploaded to the device. Returns (index, model, live_filters).

    ``trie_shards`` builds the subscription-sharded layout
    (ShardedTrieIndex, shard axis over tp when ``mesh`` is given)
    instead of the replicated trie."""
    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.router.index import ShardedTrieIndex, TrieIndex

    filters = build_filters(n_filters, rng)
    index = (ShardedTrieIndex(trie_shards, max_levels=8) if trie_shards
             else TrieIndex(max_levels=8))
    model = RouterModel(index, n_sub_slots=n_shards, K=32, M=128,
                        mesh=mesh)
    index.load(filters)
    slot_of = rng.integers(0, n_shards, len(index.filters))
    for fid in range(len(index.filters)):
        if index.filters[fid] is not None:
            model._subs.setdefault(fid, {})[int(slot_of[fid])] = 1
    model.refresh()
    live = [f for f in index.filters if f is not None]
    return index, model, live


def make_routable(index, model, warm_topic: str):
    """Single-topic subscribe→routable probe shared by the kernel and
    churn sections: a 64-row padded batch whose rows 1.. are masked out
    (length 0 + sys flag) so only the probe topic can match. Numpy args
    transfer inside the ONE dispatch rather than as separate device_put
    round trips. Warms the 64-shape program and the scatter shapes off
    the clock via ``warm_topic``."""
    import jax

    B2 = 64
    step = model._step

    def routable(topic: str):
        tok, lens, sysf, _ = index.tokenize([topic] + [""] * (B2 - 1))
        lens[1:] = 0
        sysf[1:] = True
        return step(model._trie_dev, model._rowmap_dev, model._pool_dev,
                    tok, lens, sysf)

    model.subscribe(warm_topic, 0)
    model.refresh()
    jax.block_until_ready(routable(warm_topic))
    return routable


def windowed_tps(step, args_fn, iters: int, window_n: int, B: int):
    """Steady-state throughput with a bounded in-flight window; every
    output is blocked on before leaving the window (nothing unverified
    in flight). Returns (topics/sec, last_output)."""
    import jax

    t_start = time.time()
    window = []
    last = None
    for i in range(iters):
        window.append(step(*args_fn(i)))
        if len(window) >= window_n:
            last = window.pop(0)
            jax.block_until_ready(last)
    for o in window:
        last = o
        jax.block_until_ready(o)
    return iters * B / (time.time() - t_start), last


# ---------------------------------------------------------------------------
# section: kernel (the headline — 1M-filter device match)
# ---------------------------------------------------------------------------

def sec_kernel() -> None:
    n_filters = int(os.environ.get("BENCH_FILTERS", 1_000_000))
    B = int(os.environ.get("BENCH_BATCH", 16384))
    iters = int(os.environ.get("BENCH_ITERS", 100))
    n_shards = int(os.environ.get("BENCH_SHARDS", 8192))
    window_n = int(os.environ.get("BENCH_WINDOW", 8))

    import jax

    platform = _require_tpu()
    put("kernel", kernel_platform=platform, kernel_filters=n_filters)

    rng = np.random.default_rng(42)
    t0 = time.time()
    index, model, live = build_model(n_filters, rng, n_shards)
    arrays = index.arrays
    log(f"built+loaded+uploaded {len(index.filters)} filters in "
        f"{time.time()-t0:.1f}s: nodes={arrays.n_nodes} "
        f"ht={arrays.ht_parent.shape[0]} "
        f"pool={int(model._pool_dev.nbytes) >> 10}KiB "
        f"rowmap={int(model._rowmap_dev.nbytes) >> 20}MiB "
        f"device={jax.devices()[0]}")

    # pre-tokenized topic batches (the C++ ingest host's job in production)
    n_vehicles = max(1000, n_filters // 2)
    n_batches = 8
    t0 = time.time()
    batches = []
    topics = None
    for _ in range(n_batches):
        topics = make_topics(live, rng, B, n_vehicles)
        tok, lens, sysf, too_long = index.tokenize(topics)
        assert not too_long
        batches.append(tuple(jax.device_put(x) for x in (tok, lens, sysf)))
    log(f"tokenized {n_batches}x{B} topics in {time.time()-t0:.1f}s")

    step = model._step
    trie_dev = model._trie_dev
    bm_dev = (model._rowmap_dev, model._pool_dev)

    t0 = time.time()
    out = step(trie_dev, *bm_dev, *batches[0])
    jax.block_until_ready(out)
    log(f"compile+first step {time.time()-t0:.1f}s")

    # synchronous per-step latency (the p99 a single publish batch sees);
    # sample count capped (each sync step is a host round trip)
    lat_iters = min(iters, int(os.environ.get("BENCH_LAT_ITERS", 30)))
    lat = []
    for i in range(lat_iters):
        t0 = time.time()
        out = step(trie_dev, *bm_dev, *batches[i % n_batches])
        jax.block_until_ready(out)
        lat.append(time.time() - t0)

    tps, last = windowed_tps(
        step, lambda i: (trie_dev, *bm_dev, *batches[i % n_batches]),
        iters, window_n, B)

    matched_per_topic = np.sum(np.asarray(last[0]) >= 0, axis=1)
    lat_ms = np.array(lat) * 1e3
    log(f"matched filters/topic: mean={matched_per_topic.mean():.2f} "
        f"(dense-pool rows: {len(model._dense_row)})")
    log(f"sync step latency ms: p50={np.percentile(lat_ms,50):.2f} "
        f"p99={np.percentile(lat_ms,99):.2f} (batch={B})")
    log(f"throughput (window={window_n}): {tps:,.0f} topics/sec "
        f"@ {n_filters} subs")
    put("kernel",
        kernel_topics_per_sec=round(tps),
        kernel_sync_p50_ms=round(float(np.percentile(lat_ms, 50)), 2),
        kernel_sync_p99_ms=round(float(np.percentile(lat_ms, 99)), 2))

    # measured in-repo anchor (VERDICT r2 weak #3): the host-oracle trie
    # (router/trie.py — the emqx_trie.erl semantics the kernel is
    # differentially tested against) walking the SAME topic
    # distribution. Match cost is O(topic depth), not O(filters), so a
    # subset-built trie gives the same per-topic walk cost as 1M.
    from emqx_tpu.router.trie import Trie

    n_oracle = min(len(live),
                   int(os.environ.get("BENCH_ORACLE_FILTERS", 200_000)))
    oracle = Trie()
    for f in live[:n_oracle]:
        oracle.insert(f)
    o_topics = topics[: min(len(topics), 4096)]
    t0 = time.time()
    o_hits = sum(len(oracle.match(t)) for t in o_topics)
    oracle_tps = len(o_topics) / (time.time() - t0)
    vs_oracle = tps / oracle_tps
    log(f"host-oracle anchor: {oracle_tps:,.0f} topics/sec "
        f"(python trie walk, {n_oracle} filters, {o_hits} matches) "
        f"→ device = {vs_oracle:,.1f}x the measured host oracle")
    put("kernel", vs_host_oracle=round(vs_oracle, 1))

    # -- incremental subscribe→routable latency -----------------------------
    # North star: emqx_trie.erl:113-144-style O(topic-depth) insert, NOT a
    # full rebuild (round 1: 106 s at 1M filters). Each sample: subscribe a
    # brand-new filter → scatter-patch HBM → publish a matching topic and
    # block on its fan-out.
    routable = make_routable(index, model,
                             "fleet/warm/vehicle/w/part/p0/m0")

    inc = []
    for i in range(30):
        f = f"fleet/fnew/vehicle/z{i}/part/p{i % 8}/m{i % 16}"
        t0 = time.time()
        model.subscribe(f, int(rng.integers(0, n_shards)))
        model.refresh()
        out = routable(f)
        jax.block_until_ready(out)
        inc.append(time.time() - t0)
        assert int(np.sum(np.asarray(out[0])[0] >= 0)) >= 1, \
            "new filter not routable"
    inc_ms = np.array(inc) * 1e3
    log(f"incremental subscribe→routable ms: "
        f"p50={np.percentile(inc_ms,50):.2f} "
        f"p99={np.percentile(inc_ms,99):.2f} (full uploads since load: "
        f"{model.upload_count - 1}, patches: {model.patch_count})")
    put("kernel",
        inc_sub_routable_p50_ms=round(float(np.percentile(inc_ms, 50)), 2),
        inc_sub_routable_p99_ms=round(float(np.percentile(inc_ms, 99)), 2))

    # the sync number above includes a fixed host↔device synchronization
    # cost — the amortized chain below shows the device-side update
    # cost: N dependent subscribe→patch→match chains, one block at the
    # end
    n_chain = 50
    t0 = time.time()
    out = None
    for i in range(n_chain):
        f = f"fleet/fchain/vehicle/c{i}/part/p{i % 8}/m{i % 16}"
        model.subscribe(f, int(rng.integers(0, n_shards)))
        model.refresh()
        out = routable(f)
    jax.block_until_ready(out)
    chain_ms = (time.time() - t0) * 1e3 / n_chain
    log(f"incremental update amortized (pipelined chain of {n_chain}): "
        f"{chain_ms:.2f} ms/update")
    put("kernel", inc_chain_ms=round(chain_ms, 2))

    # -- kernel-plane telemetry percentiles (round 19) ----------------------
    # drive the full submit→collect path with a DeviceMetricsFold
    # attached so the artifact records the device-clock stage split
    # (kernel_summary(), the same surface server.kernel_summary()
    # serves) next to the loadgen-free step numbers above
    from emqx_tpu.observe.device_metrics import DeviceMetricsFold
    from emqx_tpu.observe.metrics import Metrics as _Metrics

    fold = DeviceMetricsFold(_Metrics(), model=model)
    hm, model._host_matcher = model._host_matcher, None
    model.telemetry = fold
    try:
        tel_topics = make_topics(live, rng, 1024, n_vehicles)
        for _ in range(10):
            model.publish_batch_collect(
                model.publish_batch_submit(tel_topics))
    finally:
        model.telemetry = None
        model._host_matcher = hm
    ks = fold.kernel_summary()
    log(f"kernel telemetry stages us: "
        + " ".join(f"{s}=p50:{v['p50_us']}/p99:{v['p99_us']}"
                   for s, v in ks["stages"].items())
        + f" counters={ks['counters']}")
    put("kernel",
        kernel_submit_p50_us=ks["stages"]["submit"]["p50_us"],
        kernel_submit_p99_us=ks["stages"]["submit"]["p99_us"],
        kernel_step_p50_us=ks["stages"]["step"]["p50_us"],
        kernel_step_p99_us=ks["stages"]["step"]["p99_us"],
        kernel_decode_p50_us=ks["stages"]["decode"]["p50_us"],
        kernel_decode_p99_us=ks["stages"]["decode"]["p99_us"],
        kernel_telemetry_batches=ks["batches"])


# ---------------------------------------------------------------------------
# section: tenm (BASELINE config 3 — 10M subscriptions)
# ---------------------------------------------------------------------------

def _tenm_cache_dir(n: int, n_shards: int, B: int,
                    variant: str = "") -> str:
    import tempfile

    root = os.environ.get("BENCH_TENM_CACHE_DIR",
                          os.path.join(tempfile.gettempdir(),
                                       "emqx_bench_tenm"))
    # the sharded layout gets its OWN cache (variant="shN"): its vocab
    # intern order, fid namespace, rowmap/pool and tokenization all
    # differ from the replicated build's
    return os.path.join(root, f"n{n}_s{n_shards}_b{B}{variant}_v1")


_TENM_ARRAYS = ("ht_parent", "ht_word", "ht_child", "plus_child",
                "hash_fid", "node_fid", "rowmap", "pool",
                "tok", "lens", "sysf")


def _tenm_save_cache(cache: str, index, model, tok, lens, sysf) -> None:
    """Persist the host-built trie/pool arrays + the tokenized probe
    batch as individual .npy files (np.savez would defeat mmap). The
    meta file lands LAST so a killed writer never fakes a valid cache."""
    tmp = cache + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    arrays = index.ensure()
    vals = dict(
        ht_parent=arrays.ht_parent, ht_word=arrays.ht_word,
        ht_child=arrays.ht_child, plus_child=arrays.plus_child,
        hash_fid=arrays.hash_fid, node_fid=arrays.node_fid,
        rowmap=model._rowmap_host, pool=model._pool_host,
        tok=tok, lens=lens, sysf=sysf)
    for name in _TENM_ARRAYS:
        np.save(os.path.join(tmp, f"{name}.npy"), vals[name])
    live = sum(f is not None for f in index.filters)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump({"n_nodes": arrays.n_nodes,
                   "n_filters": arrays.n_filters,
                   "max_probes": arrays.max_probes,
                   "live": live}, f)
    if os.path.isdir(cache):
        import shutil
        shutil.rmtree(cache, ignore_errors=True)
    os.replace(tmp, cache)


def _tenm_load_cache(cache: str):
    """mmap-load a previously built 10M index: the device upload streams
    straight out of the page cache instead of re-running the ~6-minute
    host build (VERDICT r5 next #1: the 800s section deadline must buy
    measurement, not rebuild)."""
    with open(os.path.join(cache, "meta.json")) as f:
        meta = json.load(f)
    arrs = {name: np.load(os.path.join(cache, f"{name}.npy"),
                          mmap_mode="r")
            for name in _TENM_ARRAYS}
    from emqx_tpu.router.index import TrieIndexArrays

    arrays = TrieIndexArrays(
        ht_parent=arrs["ht_parent"], ht_word=arrs["ht_word"],
        ht_child=arrs["ht_child"], plus_child=arrs["plus_child"],
        hash_fid=arrs["hash_fid"], node_fid=arrs["node_fid"],
        n_nodes=meta["n_nodes"], n_filters=meta["n_filters"],
        max_probes=meta["max_probes"])
    return meta, arrays, arrs


_TENM_TRIE_ARRAYS = _TENM_ARRAYS[:6]
_TENM_AUX_ARRAYS = _TENM_ARRAYS[6:]


def _tenm_save_cache_sharded(cache: str, index, model,
                             tok, lens, sysf) -> None:
    """Sharded-layout twin of _tenm_save_cache: per-shard trie arrays
    under shard<k>/ plus the shared rowmap/pool/batch at the root."""
    tmp = cache + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    shard_arrays = index.ensure()      # equalized edge tables
    per_meta = []
    for k, arrays in enumerate(shard_arrays):
        d = os.path.join(tmp, f"shard{k}")
        os.makedirs(d, exist_ok=True)
        for name in _TENM_TRIE_ARRAYS:
            np.save(os.path.join(d, f"{name}.npy"), getattr(arrays, name))
        per_meta.append({"n_nodes": arrays.n_nodes,
                         "n_filters": arrays.n_filters,
                         "max_probes": arrays.max_probes})
    aux = dict(rowmap=model._rowmap_host, pool=model._pool_host,
               tok=tok, lens=lens, sysf=sysf)
    for name in _TENM_AUX_ARRAYS:
        np.save(os.path.join(tmp, f"{name}.npy"), aux[name])
    live = sum(f is not None for f in index.filters)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump({"n_shards": index.n_shards, "shards": per_meta,
                   "live": live}, f)
    if os.path.isdir(cache):
        import shutil
        shutil.rmtree(cache, ignore_errors=True)
    os.replace(tmp, cache)


def _tenm_load_cache_sharded(cache: str):
    """mmap-load a cached sharded build: (meta, shard_arrays, aux)."""
    from emqx_tpu.router.index import TrieIndexArrays

    with open(os.path.join(cache, "meta.json")) as f:
        meta = json.load(f)
    shard_arrays = []
    for k, sm in enumerate(meta["shards"]):
        d = os.path.join(cache, f"shard{k}")
        arrs = {name: np.load(os.path.join(d, f"{name}.npy"),
                              mmap_mode="r")
                for name in _TENM_TRIE_ARRAYS}
        shard_arrays.append(TrieIndexArrays(
            n_nodes=sm["n_nodes"], n_filters=sm["n_filters"],
            max_probes=sm["max_probes"], **arrs))
    aux = {name: np.load(os.path.join(cache, f"{name}.npy"),
                         mmap_mode="r")
           for name in _TENM_AUX_ARRAYS}
    return meta, shard_arrays, aux


def sec_tenm() -> None:
    """BASELINE config 3 / the north star's 10M-subscription point
    (VERDICT r3 #2: the 10M run must live in a driver artifact, not a
    commit message). Cold build + device upload + windowed kernel
    throughput + sync p99 at 10M mixed-wildcard filters.

    The host-side build serializes to disk on first success and
    mmap-loads on every later attempt (~378s → seconds)."""
    import jax

    _require_tpu()

    n = int(os.environ.get("BENCH_TENM_FILTERS", 10_000_000))
    B = int(os.environ.get("BENCH_BATCH", 16384))
    iters = int(os.environ.get("BENCH_TENM_ITERS", 30))
    n_shards = int(os.environ.get("BENCH_SHARDS", 8192))
    rng = np.random.default_rng(3)

    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.ops import trie_match as tm
    from emqx_tpu.router.index import TrieIndex

    cache = _tenm_cache_dir(n, n_shards, B)
    cached = os.path.exists(os.path.join(cache, "meta.json"))
    t0 = time.time()
    if cached:
        meta, arrays, arrs = _tenm_load_cache(cache)
        trie_dev = tm.device_trie(arrays)
        import jax.numpy as jnp
        rowmap_dev = jnp.asarray(arrs["rowmap"])
        pool_dev = jnp.asarray(arrs["pool"])
        batch = tuple(jax.device_put(np.asarray(arrs[k]))
                      for k in ("tok", "lens", "sysf"))
        # a bare model supplies the jitted step (same K/M/ret_cap/
        # max_probes statics as build_model's)
        step = RouterModel(TrieIndex(max_levels=8),
                           n_sub_slots=n_shards, K=32, M=128)._step
        n_live = meta["live"]
        build_s = time.time() - t0
        log(f"10M: mmap-loaded {n_live} cached filters in {build_s:.0f}s "
            f"({cache})")
    else:
        index, model, live = build_model(n, rng, n_shards)
        topics = make_topics(live, rng, B, max(1000, n // 2))
        tok, lens, sysf, _ = index.tokenize(topics)
        batch = tuple(jax.device_put(x) for x in (tok, lens, sysf))
        trie_dev = model._trie_dev
        rowmap_dev, pool_dev = model._rowmap_dev, model._pool_dev
        step = model._step
        n_live = len(index.filters)
        build_s = time.time() - t0
        try:
            t1 = time.time()
            _tenm_save_cache(cache, index, model, tok, lens, sysf)
            log(f"10M: cached host build to {cache} "
                f"({time.time()-t1:.0f}s)")
        except OSError as e:       # disk-full etc: cache is optional
            log(f"10M: cache write failed ({e}); continuing uncached")
    import jax.tree_util as jtu
    hbm_bytes = (int(pool_dev.nbytes) + int(rowmap_dev.nbytes)
                 + sum(int(x.nbytes) for x in jtu.tree_leaves(trie_dev)))
    log(f"10M: built+loaded+uploaded {n_live} filters in "
        f"{build_s:.0f}s, device bytes={hbm_bytes / (1 << 30):.2f} GiB")
    put("tenm", tenm_build_s=round(build_s, 1),
        tenm_index_cached=cached,
        tenm_platform=jax.devices()[0].platform,
        tenm_device_gib=round(hbm_bytes / (1 << 30), 2))
    t0 = time.time()
    out = step(trie_dev, rowmap_dev, pool_dev, *batch)
    jax.block_until_ready(out)
    log(f"10M: compile+first step {time.time() - t0:.1f}s")

    lat = []
    for _ in range(5):
        t0 = time.time()
        jax.block_until_ready(
            step(trie_dev, rowmap_dev, pool_dev, *batch))
        lat.append(time.time() - t0)
    window_n = int(os.environ.get("BENCH_WINDOW", 8))
    tps, _ = windowed_tps(
        step,
        lambda i: (trie_dev, rowmap_dev, pool_dev, *batch),
        iters, window_n, B)
    p99 = float(np.percentile(np.array(lat) * 1e3, 99))
    log(f"10M: {tps:,.0f} topics/sec (window={window_n}), sync p99 "
        f"{p99:.1f}ms @ {n} subs")
    put("tenm", tenm_topics_per_sec=round(tps),
        tenm_sync_p99_ms=round(p99, 1))
    del trie_dev, rowmap_dev, pool_dev, batch, out  # free HBM for the arm
    _tenm_sharded_arm(n, B, iters, n_shards, window_n)


def _tenm_sharded_arm(n: int, B: int, iters: int, n_shards: int,
                      window_n: int) -> None:
    """The ISSUE-17 comparison arm: the SAME 10M filter set on the
    subscription-sharded trie (ShardedTrieIndex stacked [S, ...], shard
    axis over tp at the largest available mesh), measured next to the
    replicated baseline above.  Its own disk cache — the sharded
    build's fid namespace, vocab order, rowmap/pool and tokenization
    all differ from the replicated one's."""
    import jax
    import jax.numpy as jnp

    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.ops import trie_match as tm
    from emqx_tpu.parallel import mesh as pmesh
    from emqx_tpu.router.index import ShardedTrieIndex

    rng = np.random.default_rng(3)
    n_dev = len(jax.devices())
    mesh = pmesh.make_mesh(n_dev) if n_dev >= 2 else None
    tp_ext = mesh.shape[pmesh.TP] if mesh is not None else 1
    S = int(os.environ.get("BENCH_TRIE_SHARDS", 0)) or max(4, tp_ext)
    S = max(tp_ext, S - S % tp_ext)    # shard axis must split evenly
    mesh_label = (f"{mesh.shape[pmesh.DP]}x{tp_ext}" if mesh is not None
                  else "1x1")
    shardings = pmesh.router_shardings(mesh) if mesh is not None else None

    cache = _tenm_cache_dir(n, n_shards, B, variant=f"_sh{S}")
    cached = os.path.exists(os.path.join(cache, "meta.json"))
    t0 = time.time()
    # the bare model supplies the jitted sharded step (n_shards static)
    step_model = RouterModel(
        ShardedTrieIndex(S, max_levels=8), n_sub_slots=n_shards,
        K=32, M=128, mesh=mesh)
    if cached:
        meta, shard_arrays, aux = _tenm_load_cache_sharded(cache)
        trie_dev = tm.stacked_device_trie(shard_arrays)
        rowmap_host, pool_host = aux["rowmap"], aux["pool"]
        batch_host = tuple(np.asarray(aux[k])
                           for k in ("tok", "lens", "sysf"))
        n_live = meta["live"]
    else:
        index, model, live = build_model(n, rng, n_shards, mesh=mesh,
                                         trie_shards=S)
        topics = make_topics(live, rng, B, max(1000, n // 2))
        tok, lens, sysf, _ = index.tokenize(topics)
        trie_dev = tm.stacked_device_trie(index.ensure())
        rowmap_host, pool_host = model._rowmap_host, model._pool_host
        batch_host = (tok, lens, sysf)
        n_live = sum(f is not None for f in index.filters)
        try:
            t1 = time.time()
            _tenm_save_cache_sharded(cache, index, model, tok, lens, sysf)
            log(f"10M sharded: cached host build to {cache} "
                f"({time.time()-t1:.0f}s)")
        except OSError as e:
            log(f"10M sharded: cache write failed ({e}); uncached")
    if shardings is not None:
        trie_dev = jax.device_put(trie_dev, shardings["trie_sub"])
        rowmap_dev = jax.device_put(np.asarray(rowmap_host),
                                    shardings["replicated"])
        pool_dev = jax.device_put(np.asarray(pool_host),
                                  shardings["bitmaps"])
        batch = jax.device_put(batch_host, shardings["batch_dp"])
    else:
        trie_dev = tm.DeviceTrie(*(jnp.asarray(x) for x in trie_dev))
        rowmap_dev = jnp.asarray(np.asarray(rowmap_host))
        pool_dev = jnp.asarray(np.asarray(pool_host))
        batch = tuple(jax.device_put(np.asarray(x)) for x in batch_host)
    build_s = time.time() - t0
    import jax.tree_util as jtu
    hbm_bytes = (int(pool_dev.nbytes) + int(rowmap_dev.nbytes)
                 + sum(int(x.nbytes) for x in jtu.tree_leaves(trie_dev)))
    log(f"10M sharded: S={S} mesh={mesh_label} {n_live} filters ready in "
        f"{build_s:.0f}s, device bytes={hbm_bytes / (1 << 30):.2f} GiB")
    put("tenm", tenm_sharded_shards=S, tenm_sharded_mesh=mesh_label,
        tenm_sharded_build_s=round(build_s, 1),
        tenm_sharded_index_cached=cached,
        tenm_sharded_device_gib=round(hbm_bytes / (1 << 30), 2))

    step = step_model._step
    t0 = time.time()
    jax.block_until_ready(step(trie_dev, rowmap_dev, pool_dev, *batch))
    log(f"10M sharded: compile+first step {time.time() - t0:.1f}s")
    lat = []
    for _ in range(5):
        t0 = time.time()
        jax.block_until_ready(
            step(trie_dev, rowmap_dev, pool_dev, *batch))
        lat.append(time.time() - t0)
    tps, _ = windowed_tps(
        step, lambda i: (trie_dev, rowmap_dev, pool_dev, *batch),
        iters, window_n, B)
    p99 = float(np.percentile(np.array(lat) * 1e3, 99))
    log(f"10M sharded: {tps:,.0f} topics/sec (S={S}, mesh={mesh_label}),"
        f" sync p99 {p99:.1f}ms @ {n} subs")
    put("tenm", tenm_sharded_topics_per_sec=round(tps),
        tenm_sharded_sync_p99_ms=round(p99, 1))


# ---------------------------------------------------------------------------
# section: churn (route updates under load — emqx_trie.erl:113-144 analogue)
# ---------------------------------------------------------------------------

def sec_churn() -> None:
    """On-device route churn (VERDICT r4 #6 / SURVEY §7 hard-part (a)):
    sustained subscribe/unsubscribe ops concurrent with windowed match
    launches at 1M filters. Reports ops/s, match-throughput degradation
    vs the quiescent rate from the SAME run, and subscribe→routable p99
    sampled under load. The reference's anchor is emqx_trie.erl's
    incremental insert/delete inside a live mnesia transaction stream."""
    import jax

    _require_tpu()

    n = int(os.environ.get("BENCH_CHURN_FILTERS", 1_000_000))
    B = int(os.environ.get("BENCH_BATCH", 16384))
    window_n = int(os.environ.get("BENCH_WINDOW", 8))
    n_shards = int(os.environ.get("BENCH_SHARDS", 8192))
    ops_per_round = int(os.environ.get("BENCH_CHURN_OPS_PER_ROUND", 512))
    rounds = int(os.environ.get("BENCH_CHURN_ROUNDS", 60))
    rng = np.random.default_rng(11)

    t0 = time.time()
    index, model, live = build_model(n, rng, n_shards)
    log(f"churn: built+uploaded {len(index.filters)} filters in "
        f"{time.time()-t0:.0f}s")
    put("churn", churn_filters=n)

    topics = make_topics(live, rng, B, max(1000, n // 2))
    tok, lens, sysf, _ = index.tokenize(topics)
    batch = tuple(jax.device_put(x) for x in (tok, lens, sysf))
    step = model._step

    def launch():
        return step(model._trie_dev, model._rowmap_dev, model._pool_dev,
                    *batch)

    jax.block_until_ready(launch())

    # quiescent baseline from the same run/shape
    base_iters = 30
    base_tps, _ = windowed_tps(step, lambda i: (
        model._trie_dev, model._rowmap_dev, model._pool_dev, *batch),
        base_iters, window_n, B)
    log(f"churn: quiescent baseline {base_tps:,.0f} topics/sec")

    routable = make_routable(index, model,
                             "fleet/cwarm/vehicle/w/part/p0/m0")

    # churn loop: every round does ops_per_round/2 subscribes +
    # ops_per_round/2 unsubscribes (of filters added ~8 rounds ago, so
    # the table size stays ~n), one refresh (flushes the patch batch),
    # then keeps the match window full. Every 10th round also samples a
    # full subscribe→routable latency under the running window.
    added: list[tuple[str, int]] = []
    ridx = 0
    window = []
    n_ops = 0
    sub_lat = []
    t_start = time.time()
    for r in range(rounds):
        half = ops_per_round // 2
        for i in range(half):
            f = f"fleet/churn{r}/vehicle/c{i}/part/p{i % 8}/m{i % 16}"
            slot = int((r * half + i) % n_shards)
            model.subscribe(f, slot)
            added.append((f, slot))
        while len(added) > 8 * half:
            f, slot = added.pop(0)
            model.unsubscribe(f, slot)
            n_ops += 1
        model.refresh()
        n_ops += half
        if r % 10 == 5:
            # a tracked subscribe→routable sample riding the live window
            f = f"fleet/probe/vehicle/pr{r}/part/p0/m0"
            t0 = time.time()
            model.subscribe(f, 0)
            model.refresh()
            out = routable(f)
            jax.block_until_ready(out)
            sub_lat.append(time.time() - t0)
            assert int(np.sum(np.asarray(out[0])[0] >= 0)) >= 1
            added.append((f, 0))
        window.append(launch())
        if len(window) >= window_n:
            jax.block_until_ready(window.pop(0))
    for o in window:
        jax.block_until_ready(o)
    wall = time.time() - t_start
    churn_tps = rounds * B / wall
    ops_per_sec = n_ops / wall
    ratio = churn_tps / max(base_tps, 1e-9)
    sub_ms = np.array(sub_lat) * 1e3 if sub_lat else np.array([float("nan")])
    log(f"churn: {ops_per_sec:,.0f} route ops/s sustained, match "
        f"throughput {churn_tps:,.0f} topics/sec ({ratio:.2f}x quiescent), "
        f"subscribe→routable under load p50="
        f"{np.percentile(sub_ms,50):.1f}ms p99={np.percentile(sub_ms,99):.1f}ms "
        f"(patches: {model.patch_count}, uploads: {model.upload_count})")
    put("churn",
        churn_ops_per_sec=round(ops_per_sec),
        churn_match_topics_per_sec=round(churn_tps),
        churn_match_vs_quiescent=round(ratio, 2),
        churn_sub_routable_p50_ms=round(float(np.percentile(sub_ms, 50)), 2),
        churn_sub_routable_p99_ms=round(float(np.percentile(sub_ms, 99)), 2))


# ---------------------------------------------------------------------------
# sections: crossover study (C++ per-message walk vs device kernel)
# ---------------------------------------------------------------------------

CROSS_SIZES = tuple(
    int(x) for x in os.environ.get(
        "BENCH_CROSS_SIZES", "1000,100000,1000000").split(","))


def sec_xdev() -> None:
    """Device half of the crossover study (VERDICT r4 #3): the kernel's
    windowed throughput at the sub-1M table sizes (the 1M point comes
    from the kernel section itself; composed by the supervisor)."""
    import jax

    _require_tpu()

    B = int(os.environ.get("BENCH_BATCH", 16384))
    window_n = int(os.environ.get("BENCH_WINDOW", 8))
    iters = int(os.environ.get("BENCH_XDEV_ITERS", 40))
    for n in CROSS_SIZES[:-1]:
        rng = np.random.default_rng(100 + n % 97)
        index, model, live = build_model(n, rng, 8192)
        topics = make_topics(live, rng, B, max(1000, n // 2))
        tok, lens, sysf, _ = index.tokenize(topics)
        batch = tuple(jax.device_put(x) for x in (tok, lens, sysf))
        step = model._step
        jax.block_until_ready(step(
            model._trie_dev, model._rowmap_dev, model._pool_dev, *batch))
        tps, _ = windowed_tps(step, lambda i: (
            model._trie_dev, model._rowmap_dev, model._pool_dev, *batch),
            iters, window_n, B)
        log(f"xdev: {tps:,.0f} topics/sec @ {n} filters")
        put("xdev", **{f"dev_match_tps_{n}": round(tps)})


def sec_xcpp() -> None:
    """C++ half of the crossover study: the per-message trie walk
    (native/src/router.h SubTable::Match — the same code the epoll fast
    path runs per PUBLISH) against the same filter distribution at
    1k/100k/1M, in the emqx_broker_bench.erl:run1/4 shape (topics
    published into a wildcard-dense subscribed tree). Single core, bulk
    C call so ctypes overhead stays off the measurement."""
    from emqx_tpu import native

    if not native.available():
        log(f"xcpp: native lib unavailable: {native.build_error()}")
        return

    n_topics = int(os.environ.get("BENCH_XCPP_TOPICS", 65_536))
    for n in CROSS_SIZES:
        rng = np.random.default_rng(100 + n % 97)
        filters = build_filters(n, rng)
        tab = native.NativeSubTable()
        t0 = time.time()
        for i, f in enumerate(filters):
            tab.add(i, f)
        build_s = time.time() - t0
        live = sorted(set(filters))
        topics = make_topics(live, rng, n_topics, max(1000, n // 2))
        tab.match_many(topics[:1024])  # warm caches
        t0 = time.time()
        reps = 0
        matches = 0
        while time.time() - t0 < 2.0:
            _, m = tab.match_many(topics)
            matches += m
            reps += 1
        dt = time.time() - t0
        tps = reps * len(topics) / dt
        log(f"xcpp: {tps:,.0f} topics/sec @ {n} filters "
            f"({matches / (reps * len(topics)):.2f} matches/topic, "
            f"table build {build_s:.1f}s, single core)")
        put("xcpp", **{f"cpp_match_tps_{n}": round(tps)})
        tab.close()


# ---------------------------------------------------------------------------
# section: shared subscriptions + retained (BASELINE config 4)
# ---------------------------------------------------------------------------

def sec_shared() -> None:
    """BASELINE config 4: shared subscriptions + retained messages at
    100K groups. Measures strategy-pick dispatch throughput across the
    group table (emqx_shared_sub.erl:138-157) and wildcard retained
    lookup against a populated store (emqx_retainer_index semantics)."""
    import time as _time

    from emqx_tpu.broker.shared_sub import SharedSub
    from emqx_tpu.core.message import Message
    from emqx_tpu.services.retainer import Retainer

    n_groups = int(os.environ.get("BENCH_GROUPS", 100_000))
    members_per = int(os.environ.get("BENCH_GROUP_MEMBERS", 4))
    rng = np.random.default_rng(7)

    shared = SharedSub(node="bench", strategy="round_robin")
    t0 = _time.time()
    for g in range(n_groups):
        topic = f"fleet/f{g % 512}/group{g}/+"
        for m in range(members_per):
            shared.join(f"g{g}", topic, f"sess-{g}-{m}", node="bench")
    log(f"shared: {n_groups} groups x {members_per} members joined "
        f"in {_time.time()-t0:.1f}s")

    picks = [int(x) for x in rng.integers(0, n_groups, 50_000)]
    msg = Message(topic="x", payload=b"p")
    t0 = _time.time()
    n_dispatched = 0
    for g in picks:
        # dispatch is keyed by the subscribed FILTER (the route topic),
        # exactly as broker._route hands it over
        got = shared.dispatch(f"g{g}", f"fleet/f{g % 512}/group{g}/+",
                              msg, deliver_fn=lambda s, n: True)
        n_dispatched += len(got)
    dt = _time.time() - t0
    log(f"shared dispatch (python, per-message): "
        f"{len(picks)/dt:,.0f} dispatches/sec @ {n_groups} groups "
        f"({n_dispatched} deliveries)")
    legs = [(f"g{g}", f"fleet/f{g % 512}/group{g}/+", msg) for g in picks]
    t0 = _time.time()
    out = shared.dispatch_batch(legs)
    dt = _time.time() - t0
    log(f"shared dispatch (python, batched): "
        f"{len(legs)/dt:,.0f} dispatches/sec "
        f"({sum(o is not None for o in out)} picks)")
    # the native C++ dispatcher — the path that actually serves fully
    # native groups in the broker (host.cc SharedGroup; VERDICT r3 #7)
    from emqx_tpu import native as _native
    if _native.available():
        tab = _native.NativeSubTable()
        for g in range(n_groups):
            filt = f"fleet/f{g % 512}/group{g}/+"
            for m in range(members_per):
                tab.shared_add(g + 1, (g << 3) | m, filt)
        topics = [f"fleet/f{g % 512}/group{g}/x"
                  for g in rng.integers(0, n_groups, 500_000)]
        t0 = _time.time()
        n_t, n_picks = tab.shared_pick_many(topics)
        dt = _time.time() - t0
        log(f"shared dispatch (native C++, incl. full topic match): "
            f"{n_picks/dt:,.0f} picks/sec @ {n_groups} groups")
        put("shared", shared_native_picks_per_sec=round(n_picks / dt))
        tab.close()

    retainer = Retainer(max_retained=n_groups + 10)
    t0 = _time.time()
    for g in range(n_groups):
        retainer.store(Message(
            topic=f"fleet/f{g % 512}/group{g}/state", payload=b"s",
            flags={"retain": True}))
    log(f"retainer: {n_groups} retained in {_time.time()-t0:.1f}s")
    t0 = _time.time()
    n_cold = sum(len(retainer.match(f"fleet/f{f}/+/state"))
                 for f in range(512))
    cold_dt = _time.time() - t0
    # steady state: the per-bucket submatrix caches are warm (retained
    # dispatch on subscribe hits the same buckets continuously)
    reps = 10
    t0 = _time.time()
    n_hits = 0
    for _ in range(reps):
        for f in range(512):
            n_hits += len(retainer.match(f"fleet/f{f}/+/state"))
    dt = _time.time() - t0
    log(f"retained wildcard lookup: {reps*512/dt:,.0f} lookups/sec warm "
        f"({512/cold_dt:,.0f} cold) = {n_hits/dt:,.0f} matched msgs/sec "
        f"(~{n_hits//(512*reps)} matches per lookup @ {n_groups} "
        f"retained; vectorized store, VERDICT r3 #5)")
    put("shared",
        retained_lookups_per_sec=round(reps * 512 / dt),
        retained_lookups_per_sec_cold=round(512 / cold_dt))


# ---------------------------------------------------------------------------
# section: host plane (C++ epoll data plane; CPU by design)
# ---------------------------------------------------------------------------

def sec_host() -> None:
    """VERDICT r3 #1 before/after: the round-3 configuration (asyncio
    server, Python clients — measured 14k msg/s host path, 5.5k e2e)
    against the round-4 C++ data plane (epoll host with the native
    PUBLISH fast path, driven by the C++ loadgen — the emqtt-bench
    analogue; a Python client fleet would measure itself, not the
    broker). Reference anchor: 1M msg/s sustained (README.md:16),
    sub-ms latency. Every number here measures the C++ data plane on
    the host CPU by design."""
    import asyncio

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.broker.server import BrokerServer
    from emqx_tpu.mqtt.client import MqttClient

    n_msg_before = int(os.environ.get("BENCH_HOST_BEFORE_MSGS", 1500))
    n_msg_blast = int(os.environ.get("BENCH_HOST_BLAST_MSGS", 40000))

    # -- before: asyncio server + python clients (the r3 shape) -------------
    async def drive_python_clients(port) -> float:
        subs = [MqttClient(port=port, clientid=f"ns{i}") for i in range(8)]
        for i, s in enumerate(subs):
            await s.connect()
            await s.subscribe(f"lg/{i}/+", qos=0)
        pubs = [MqttClient(port=port, clientid=f"np{i}") for i in range(8)]
        for p in pubs:
            await p.connect()
        expected = 8 * n_msg_before
        got = 0
        done = asyncio.Event()

        async def drain(s):
            nonlocal got
            while got < expected:
                try:
                    await s.recv(timeout=10)
                except asyncio.TimeoutError:
                    break
                got += 1
                if got >= expected:
                    done.set()
        drains = [asyncio.create_task(drain(s)) for s in subs]

        async def blast(i, p):
            for j in range(n_msg_before):
                await p.publish(f"lg/{(i + j) % 8}/m", b"x" * 16, qos=0)
        t0 = time.time()
        await asyncio.gather(*(blast(i, p) for i, p in enumerate(pubs)))
        try:
            await asyncio.wait_for(done.wait(), timeout=60)
        except asyncio.TimeoutError:
            pass
        wall = time.time() - t0
        for d in drains:
            d.cancel()
        for c in subs + pubs:
            try:
                await c.disconnect()
            except Exception:
                pass
        return got / wall

    async def run_before() -> float:
        server = BrokerServer(port=0, app=BrokerApp())
        await server.start()
        try:
            return await drive_python_clients(server.port)
        finally:
            await server.stop()

    before = asyncio.run(run_before())
    log(f"host plane BEFORE (asyncio + python clients, qos0): "
        f"{before:,.0f} msg/s")
    put("host", e2e_host_before_msgs_per_sec=round(before))

    # -- after: C++ epoll host + native fast path + C++ loadgen -------------
    # mqtt.max_inflight is a zone knob (emqx_schema default 32): the
    # reference's 1M msg/s runs tune it up, and the windowed qos1/2
    # sweep measures the broker, not a 16-slot default window — so the
    # bench app raises it (the native/python planes split this budget
    # dynamically per ack cycle, see native_server._on_ack_batch)
    server = NativeBrokerServer(port=0, app=BrokerApp(),
                                session_opts={"max_inflight": 1024})
    server.start()
    try:
        blast = native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast, qos=0, payload_len=16)
        wall = blast["wall_ns"] / 1e9
        blast_rate = blast["received"] / max(wall, 1e-9)
        log(f"host plane AFTER (C++ fast path, blast qos0): "
            f"{blast['received']}/{blast['sent']} in {wall:.2f}s = "
            f"{blast_rate:,.0f} msg/s  ({blast_rate / max(before, 1):,.0f}x "
            f"before, {blast_rate / 1e6:.2f}x the reference's 1M/s headline)")
        put("host", e2e_host_msgs_per_sec=round(blast_rate))

        lat = native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=3000, qos=0, payload_len=16, window=64)
        lat_wall = lat["wall_ns"] / 1e9
        log(f"host plane latency (windowed 64, qos0): "
            f"{lat['received'] / max(lat_wall, 1e-9):,.0f} msg/s  "
            f"p50={lat['p50_ns'] / 1e6:.3f}ms p99={lat['p99_ns'] / 1e6:.3f}ms")
        put("host",
            e2e_host_p50_ms=round(lat["p50_ns"] / 1e6, 3),
            e2e_host_p99_ms=round(lat["p99_ns"] / 1e6, 3))

        # qos1 window sweep (VERDICT r4 #8 / r5 next #10): at a fixed
        # service rate the p99 is dominated by Little's-law queueing
        # (window / rate). Every point lands suffixed; the UNSUFFIXED
        # headline is the best rate among points meeting the 2ms p99
        # budget (the VERDICT #10 acceptance shape) — or, when no point
        # qualifies (e.g. a starved CI box), the max-rate point with
        # its honest p99.
        best = None          # (rate, p99_ms) best under the 2ms budget
        peak = None          # max-rate fallback
        for win in (256, 512, 1024, 2048, 4096):
            q1 = native.loadgen_run(
                "127.0.0.1", server.port, n_subs=8, n_pubs=8,
                msgs_per_pub=n_msg_blast // 2, qos=1, payload_len=16,
                window=win)
            q1_wall = q1["wall_ns"] / 1e9
            q1_rate = q1["received"] / max(q1_wall, 1e-9)
            q1_p99 = q1["p99_ns"] / 1e6
            log(f"host plane qos1 (windowed {win}): {q1_rate:,.0f} msg/s "
                f"acks={q1['acks']} p99={q1_p99:.2f}ms")
            if q1_p99 <= 2.0 and (best is None or q1_rate > best[0]):
                best = (q1_rate, q1_p99)
            if peak is None or q1_rate > peak[0]:
                peak = (q1_rate, q1_p99)
            # headline keys ride EVERY flush (running best-so-far): a
            # deadline kill mid-sweep must still leave a headline in
            # the artifact, not just suffixed points
            head = best or peak
            put("host", **{
                f"e2e_host_qos1_msgs_per_sec_w{win}": round(q1_rate),
                f"e2e_host_qos1_p99_ms_w{win}": round(q1_p99, 3),
                "e2e_host_qos1_msgs_per_sec": round(head[0]),
                "e2e_host_qos1_p99_ms": round(head[1], 3),
                "e2e_host_qos1_within_p99_budget": bool(best)})
        head = best or peak
        log(f"host plane qos1 headline: {head[0]:,.0f} msg/s "
            f"p99={head[1]:.2f}ms"
            + ("" if best else "  (NO point met the 2ms budget)"))

        # qos2 e2e (round 6): the native exactly-once plane. Prior
        # rounds ran qos2 entirely in Python (~5k msg/s, VERDICT r5
        # missing #2); the four-packet exchange now lives in C++
        # (host.cc awaiting-rel bitmap + PUBREC/PUBREL/PUBCOMP), so
        # qos2_fast_in must move and the rate must sit well above the
        # Python plane's ceiling.
        q2 = native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast // 4, qos=2, payload_len=16,
            window=1024)
        q2_wall = q2["wall_ns"] / 1e9
        q2_rate = q2["received"] / max(q2_wall, 1e-9)
        st = server.fast_stats()
        log(f"host plane qos2 (windowed 1024): {q2_rate:,.0f} msg/s "
            f"p99={q2['p99_ns'] / 1e6:.2f}ms "
            f"qos2_fast_in={st['qos2_in']} qos2_rel={st['qos2_rel']} "
            f"({q2_rate / 5311:.0f}x the r05 python-only qos2 rate)")
        put("host",
            e2e_host_qos2_msgs_per_sec=round(q2_rate),
            e2e_host_qos2_p99_ms=round(q2["p99_ns"] / 1e6, 3),
            qos2_fast_in=st["qos2_in"],
            qos2_rel_native=st["qos2_rel"])
        # broker-side stage percentiles, cumulative across this
        # server's blast/latency/qos1-sweep/qos2 runs (ingress→route,
        # route→flush, qos1/qos2 ack RTT, GIL stint)
        summ = put_broker_hists("host", server, "broker")
        for stage in ("ingress_route", "qos1_rtt", "qos2_rtt"):
            if stage in summ:
                s = summ[stage]
                log(f"broker-side {stage}: p50={s['p50_us']:.1f}us "
                    f"p99={s['p99_us']:.1f}us p999={s['p999_us']:.1f}us "
                    f"(n={s['count']})")
        log(f"fast stats: {st}")
    finally:
        server.stop()

    # -- broad-rule cliff (VERDICT r4 #5) -----------------------------------
    # One FROM '#' console rule used to de-permit the entire fast path
    # (→ ~13k msg/s, a 130x cliff). With rule taps the ruled plane must
    # retain the bulk of the fast-path rate while the rule's copies
    # flow to the runtime (bounded queue; overload counts tap_dropped).
    app2 = BrokerApp()
    app2.rules.create_rule("bench_all", 'SELECT topic FROM "#"',
                           [{"function": "console", "args": {}}])
    server = NativeBrokerServer(port=0, app=app2)
    server.start()
    try:
        rb = native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast, qos=0, payload_len=16)
        rb_wall = rb["wall_ns"] / 1e9
        rb_rate = rb["received"] / max(rb_wall, 1e-9)
        st = server.fast_stats()
        rule_m = app2.rules.metrics.get("bench_all", "matched")
        log(f"host plane qos0 with ONE 'FROM \"#\"' rule (taps): "
            f"{rb_rate:,.0f} msg/s ({rb_rate / max(blast_rate, 1):.2f}x "
            f"the rule-free rate) taps={st['taps']} "
            f"rule_matched={rule_m} tap_dropped={server.tap_dropped}")
        put("host",
            rule_tap_msgs_per_sec=round(rb_rate),
            rule_tap_vs_free=round(rb_rate / max(blast_rate, 1), 2),
            rule_tap_dropped=server.tap_dropped)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# section: ws (MQTT-over-WebSocket on the native plane; CPU by design)
# ---------------------------------------------------------------------------

def sec_ws() -> None:
    """Round-7 tentpole before/after: the asyncio WS plane (ws.py —
    every WS client inherited the ~14k msg/s GIL ceiling while native
    TCP did 1.7M) against RFC6455 in the C++ host (ws.h + host.cc),
    driven by the loadgen's ws mode (masked frames, nonzero keys, so
    the broker pays the real unmask cost). Acceptance (ISSUE 2):
    native-WS >= 0.5x the native-TCP blast on the same box and >= 10x
    the asyncio WS plane."""
    import asyncio
    import base64

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.broker.ws import (OP_BINARY, FrameDecoder,
                                    WsBrokerServer, encode_frame)
    from emqx_tpu.mqtt import packet as P
    from emqx_tpu.mqtt.frame import Parser, serialize

    n_msg_before = int(os.environ.get("BENCH_WS_BEFORE_MSGS", 1200))
    n_msg_blast = int(os.environ.get("BENCH_WS_BLAST_MSGS", 40000))

    # -- before: asyncio WS listener + python ws clients --------------------
    class _WsClient:
        def __init__(self, port):
            self.port = port
            self.dec = FrameDecoder(require_mask=False)
            self.parser = Parser()
            self.inbox: list = []

        async def connect(self, cid):
            self.r, self.w = await asyncio.open_connection(
                "127.0.0.1", self.port)
            key = base64.b64encode(os.urandom(16)).decode()
            self.w.write((
                "GET /mqtt HTTP/1.1\r\nHost: x\r\n"
                "Upgrade: websocket\r\nConnection: Upgrade\r\n"
                f"Sec-WebSocket-Key: {key}\r\n"
                "Sec-WebSocket-Protocol: mqtt\r\n\r\n").encode())
            await self.r.readuntil(b"\r\n\r\n")
            await self.send(P.Connect(clientid=cid))
            await self.recv()
            return self

        async def send(self, pkt):
            self.w.write(encode_frame(
                OP_BINARY, serialize(pkt, P.MQTT_V4), mask=True))
            await self.w.drain()

        async def recv(self, timeout=10):
            while not self.inbox:
                data = await asyncio.wait_for(self.r.read(65536), timeout)
                assert data
                for op, payload in self.dec.feed(data):
                    if op == OP_BINARY:
                        self.inbox.extend(self.parser.feed(payload))
            return self.inbox.pop(0)

    async def run_before() -> float:
        server = WsBrokerServer(port=0, app=BrokerApp())
        await server.start()
        try:
            subs = [await _WsClient(server.port).connect(f"ws{i}")
                    for i in range(8)]
            for i, s in enumerate(subs):
                await s.send(P.Subscribe(packet_id=1,
                                         topic_filters=[(f"lg/{i}/+",
                                                         {"qos": 0})]))
                await s.recv()
            pubs = [await _WsClient(server.port).connect(f"wp{i}")
                    for i in range(8)]
            expected = 8 * n_msg_before
            got = 0
            done = asyncio.Event()

            async def drain(s):
                nonlocal got
                while got < expected:
                    try:
                        await s.recv(timeout=10)
                    except asyncio.TimeoutError:
                        break
                    got += 1
                    if got >= expected:
                        done.set()
            drains = [asyncio.create_task(drain(s)) for s in subs]

            async def blast(i, p):
                for j in range(n_msg_before):
                    await p.send(P.Publish(topic=f"lg/{(i + j) % 8}/m",
                                           payload=b"x" * 16, qos=0))
            t0 = time.time()
            await asyncio.gather(*(blast(i, p) for i, p in enumerate(pubs)))
            try:
                await asyncio.wait_for(done.wait(), timeout=60)
            except asyncio.TimeoutError:
                pass
            wall = time.time() - t0
            for d in drains:
                d.cancel()
            for c in subs + pubs:
                c.w.close()
            return got / wall
        finally:
            await server.stop()

    before = asyncio.run(run_before())
    log(f"ws plane BEFORE (asyncio + python ws clients, qos0): "
        f"{before:,.0f} msg/s")
    put("ws", ws_asyncio_msgs_per_sec=round(before))

    # -- after: C++ RFC6455 listener + ws loadgen ---------------------------
    server = NativeBrokerServer(port=0, app=BrokerApp(), ws_port=0,
                                session_opts={"max_inflight": 1024})
    server.start()
    try:
        # same-box native-TCP anchor (the ws_vs_native_tcp denominator
        # must come from THIS box/run, not a stale artifact)
        tcp = native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast, qos=0, payload_len=16)
        tcp_rate = tcp["received"] / max(tcp["wall_ns"] / 1e9, 1e-9)

        ws = native.loadgen_run(
            "127.0.0.1", server.ws_port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast, qos=0, payload_len=16, ws=True)
        ws_wall = ws["wall_ns"] / 1e9
        ws_rate = ws["received"] / max(ws_wall, 1e-9)
        log(f"ws plane AFTER (C++ RFC6455 + fast path, blast qos0): "
            f"{ws['received']}/{ws['sent']} in {ws_wall:.2f}s = "
            f"{ws_rate:,.0f} msg/s  ({ws_rate / max(before, 1):,.0f}x "
            f"asyncio-ws, {ws_rate / max(tcp_rate, 1):.2f}x native-tcp "
            f"same box)")
        put("ws",
            ws_native_msgs_per_sec=round(ws_rate),
            ws_vs_native_tcp=round(ws_rate / max(tcp_rate, 1), 2),
            ws_vs_asyncio=round(ws_rate / max(before, 1), 1))

        lat = native.loadgen_run(
            "127.0.0.1", server.ws_port, n_subs=8, n_pubs=8,
            msgs_per_pub=3000, qos=0, payload_len=16, window=64, ws=True)
        log(f"ws plane latency (windowed 64, qos0): "
            f"p50={lat['p50_ns'] / 1e6:.3f}ms "
            f"p99={lat['p99_ns'] / 1e6:.3f}ms")
        put("ws",
            ws_native_p50_ms=round(lat["p50_ns"] / 1e6, 3),
            ws_native_p99_ms=round(lat["p99_ns"] / 1e6, 3))

        q1 = native.loadgen_run(
            "127.0.0.1", server.ws_port, n_subs=8, n_pubs=8,
            msgs_per_pub=n_msg_blast // 4, qos=1, payload_len=16,
            window=1024, ws=True)
        q1_rate = q1["received"] / max(q1["wall_ns"] / 1e9, 1e-9)
        st = server.fast_stats()
        log(f"ws plane qos1 (windowed 1024): {q1_rate:,.0f} msg/s "
            f"acks={q1['acks']} p99={q1['p99_ns'] / 1e6:.2f}ms  "
            f"ws_handshakes={st['ws_handshakes']}")
        put("ws",
            ws_native_qos1_msgs_per_sec=round(q1_rate),
            ws_native_qos1_p99_ms=round(q1["p99_ns"] / 1e6, 3),
            ws_handshakes=st["ws_handshakes"])
        # broker-side stages incl. ws_ingest (what RFC6455 adds per
        # read chunk on top of the shared TCP fast path)
        put_broker_hists("ws", server, "ws_broker")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# section: observe_overhead (telemetry plane cost; CPU by design)
# ---------------------------------------------------------------------------

def _observe_overhead_kernel() -> None:
    """Kernel-counters overhead pair (round 19): publish_batch
    submit→collect throughput with in-kernel counters + the host fold
    ON vs OFF. Same interleaved alternating-order best-of-N convention
    as the native pairs — the two models differ ONLY by the
    kernel_telemetry flag (the EMQX_TPU_KERNEL_TELEMETRY switch)."""
    from emqx_tpu.models.router_model import RouterModel
    from emqx_tpu.observe.device_metrics import DeviceMetricsFold
    from emqx_tpu.observe.metrics import Metrics as _Metrics
    from emqx_tpu.router.index import TrieIndex

    n_filters = int(os.environ.get("BENCH_OBS_KERNEL_FILTERS", 20000))
    B = int(os.environ.get("BENCH_OBS_KERNEL_BATCH", 2048))
    n_batches = int(os.environ.get("BENCH_OBS_KERNEL_BATCHES", 20))
    reps = int(os.environ.get("BENCH_OBS_REPS", 3))
    rng = np.random.default_rng(7)
    filters = build_filters(n_filters, rng)
    n_vehicles = max(1000, n_filters // 2)

    models = {}
    for arm, flag in (("on", True), ("off", False)):
        index = TrieIndex(max_levels=8)
        model = RouterModel(index, n_sub_slots=64, K=32, M=128,
                            kernel_telemetry=flag)
        index.load(filters)
        for fid in range(len(index.filters)):
            if index.filters[fid] is not None:
                model._subs.setdefault(fid, {})[fid % 64] = 1
        model.refresh()
        model._host_matcher = None    # force the device path on cpu
        if flag:
            model.telemetry = DeviceMetricsFold(_Metrics(), model=model)
        models[arm] = model

    live = [f for f in filters]
    topic_sets = [make_topics(live, rng, B, n_vehicles)
                  for _ in range(4)]
    for model in models.values():      # compile off the clock
        model.publish_batch_collect(
            model.publish_batch_submit(topic_sets[0]))

    best = {"on": 0.0, "off": 0.0}
    for rep in range(reps):
        arms = ("on", "off") if rep % 2 == 0 else ("off", "on")
        for arm in arms:
            model = models[arm]
            t0 = time.time()
            for i in range(n_batches):
                model.publish_batch_collect(
                    model.publish_batch_submit(
                        topic_sets[i % len(topic_sets)]))
            rate = n_batches * B / (time.time() - t0)
            best[arm] = max(best[arm], rate)
            log(f"observe_overhead rep{rep} kernel_counters={arm}: "
                f"{rate:,.0f} topics/s")
    overhead = 1.0 - best["on"] / max(best["off"], 1e-9)
    log(f"observe_overhead kernel counters: on={best['on']:,.0f} "
        f"off={best['off']:,.0f} topics/s  "
        f"overhead={overhead * 100:.2f}% "
        f"({'within' if overhead < 0.02 else 'OVER'} the 2% budget)")
    put("observe_overhead",
        kernel_counters_on_topics_per_sec=round(best["on"]),
        kernel_counters_off_topics_per_sec=round(best["off"]),
        kernel_counters_overhead_frac=round(overhead, 4),
        kernel_counters_within_2pct_budget=bool(overhead < 0.02))


def sec_observe_overhead() -> None:
    """ISSUE 3 acceptance: the native telemetry plane (histograms +
    flight recorders + kind-8 export) must cost < 2% QoS0 native-TCP
    throughput against the EMQX_NATIVE_TELEMETRY=0 escape hatch.
    Best-of-3 per arm, interleaved, same box — the arms differ ONLY by
    the telemetry toggle (NativeBrokerServer(telemetry=...), the same
    switch the env var drives).

    ISSUE 8 acceptance: a second interleaved pair on the 2-SHARD qos0
    fan-out measures the distributed-tracing sampler — sampled tracing
    ON (1-in-64, the production default) vs OFF must also land within
    the 2% budget.

    ISSUE 19 acceptance: a third interleaved pair on the DEVICE router
    path measures the in-kernel counters + host fold
    (kernel_telemetry=True with a DeviceMetricsFold attached vs False)
    — the counters ride the existing collect device_get, so they must
    also land within the 2% budget. Model-plane only: runs even when
    the native host is unavailable."""
    _observe_overhead_kernel()

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer

    n_msg = int(os.environ.get("BENCH_OBS_MSGS", 40000))
    reps = int(os.environ.get("BENCH_OBS_REPS", 3))
    best = {"on": 0.0, "off": 0.0}
    for rep in range(reps):
        # alternate the pair order per rep (round 13): on a warming box
        # the SECOND arm of every pair wins systematically, and that
        # drift measured bigger than the effect under test
        arms = ("on", "off") if rep % 2 == 0 else ("off", "on")
        for arm in arms:                 # interleaved: drift hits both
            server = NativeBrokerServer(
                port=0, app=BrokerApp(), telemetry=(arm == "on"),
                session_opts={"max_inflight": 1024})
            server.start()
            try:
                r = native.loadgen_run(
                    "127.0.0.1", server.port, n_subs=8, n_pubs=8,
                    msgs_per_pub=n_msg, qos=0, payload_len=16)
                rate = r["received"] / max(r["wall_ns"] / 1e9, 1e-9)
                best[arm] = max(best[arm], rate)
                log(f"observe_overhead rep{rep} telemetry={arm}: "
                    f"{rate:,.0f} msg/s")
            finally:
                server.stop()
    overhead = 1.0 - best["on"] / max(best["off"], 1e-9)
    log(f"observe_overhead: on={best['on']:,.0f} off={best['off']:,.0f} "
        f"msg/s  overhead={overhead * 100:.2f}% "
        f"({'within' if overhead < 0.02 else 'OVER'} the 2% budget)")

    # -- tracing arm (ISSUE 8): 1-in-64 sampler on the 2-shard fan-out.
    # Two poll threads + the loadgen fleet oversubscribe the 2-core
    # container far harder than the single-host pair above, so this
    # pair runs a smaller fleet (4x4) and more interleaved reps — the
    # best-of convention needs both arms to find their scheduling peak.
    tbest = {"on": 0.0, "off": 0.0}
    tspans = 0
    treps = max(reps, int(os.environ.get("BENCH_OBS_TRACE_REPS", 5)))
    for rep in range(treps):
        # alternate the pair order per rep: on a warming box the SECOND
        # arm of every pair otherwise wins systematically (measured —
        # the drift was bigger than the effect under test)
        arms = ("on", "off") if rep % 2 == 0 else ("off", "on")
        for arm in arms:
            server = NativeBrokerServer(
                port=0, app=BrokerApp(), shards=2,
                tracing=(arm == "on"), trace_sample_shift=6,
                session_opts={"max_inflight": 1024})
            server.start()
            try:
                r = native.loadgen_run(
                    "127.0.0.1", server.port, n_subs=4, n_pubs=4,
                    msgs_per_pub=n_msg, qos=0, payload_len=16)
                rate = r["received"] / max(r["wall_ns"] / 1e9, 1e-9)
                tbest[arm] = max(tbest[arm], rate)
                if arm == "on":
                    tspans = max(tspans,
                                 server.fast_stats()["traced_pubs"])
                log(f"observe_overhead rep{rep} tracing={arm} "
                    f"(2 shards): {rate:,.0f} msg/s")
            finally:
                server.stop()
    t_overhead = 1.0 - tbest["on"] / max(tbest["off"], 1e-9)
    log(f"observe_overhead tracing (2-shard qos0 fan-out): "
        f"on={tbest['on']:,.0f} off={tbest['off']:,.0f} msg/s  "
        f"overhead={t_overhead * 100:.2f}% sampled={tspans} "
        f"({'within' if t_overhead < 0.02 else 'OVER'} the 2% budget)")
    put("observe_overhead",
        qos0_msgs_per_sec_telemetry_on=round(best["on"]),
        qos0_msgs_per_sec_telemetry_off=round(best["off"]),
        overhead_frac=round(overhead, 4),
        within_2pct_budget=bool(overhead < 0.02),
        shard2_qos0_msgs_per_sec_tracing_on=round(tbest["on"]),
        shard2_qos0_msgs_per_sec_tracing_off=round(tbest["off"]),
        tracing_overhead_frac=round(t_overhead, 4),
        tracing_sampled_pubs=int(tspans),
        tracing_within_2pct_budget=bool(t_overhead < 0.02))


# ---------------------------------------------------------------------------
# section: conn_scale (C10M axis: the million-connection broker; CPU by
# design — the plane under test is the C++ epoll host)
# ---------------------------------------------------------------------------

def _rss_bytes() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def _malloc_trim() -> None:
    import ctypes
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass


def sec_conn_scale() -> None:
    """ISSUE 12 acceptance: the conn-scale plane (wheel.h + park.h).

    Arm A (real sockets, full broker): a connect storm of mostly-idle
    clients against a NativeBrokerServer, held with staggered
    keepalives while a small loadgen fleet measures fan-out throughput
    — the gate is fan-out within 10% of the unloaded number while the
    herd idles, keepalive p99 honored (ping RTT p99 + zero broker
    closes), and measured RSS/conn. The herd size is fd-capped: this
    container pins RLIMIT_NOFILE at 20k (hard), so the in-process
    ceiling is ~9k conn PAIRS — recorded in the artifact.

    Arm B (raw host, synthetic sockets): the conn-scale structures at
    the ROADMAP's 1M scale. emqx_host_synth_conns drives 10^6 conns
    through the REAL admission + park machinery (fd-less conns whose
    egress is discarded), measuring resident vs parked RSS/conn, the
    parked-record gauge, and the housekeep cost with 1M armed timers —
    against a projection of the old O(N) per-housekeep sweep."""
    import resource
    import threading
    import ctypes as ct

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    put("conn_scale", conn_scale_fd_limit=soft)
    n_real = int(os.environ.get("BENCH_CONN_REAL_N",
                                max(1000, min(8000, (soft - 2000) // 2))))
    n_synth = int(os.environ.get("BENCH_CONN_SYNTH_N", 1_000_000))

    # -- arm A: real sockets through the full broker --------------------
    server = NativeBrokerServer(port=0, app=BrokerApp(),
                                park_after_ms=3000, accept_burst=512)
    server.start()
    try:
        fan_args = dict(n_subs=4, n_pubs=4, msgs_per_pub=int(
            os.environ.get("BENCH_CONN_FAN_MSGS", 8000)),
            qos=0, payload_len=16, window=0, warmup=True, salt=700000)
        reps = int(os.environ.get("BENCH_CONN_FAN_REPS", 3))

        def fan_best() -> float:
            # best-of-N: this box's identical-config throughput swings
            # more than the 10% under test (the round-13 lesson), so
            # each arm reports its PEAK capacity
            best = 0.0
            for _ in range(reps):
                r = native.loadgen_run("127.0.0.1", server.port,
                                       **fan_args)
                best = max(best,
                           r["received"] / max(r["wall_ns"], 1) * 1e9)
            return best

        base_rate = fan_best()
        put("conn_scale",
            conn_scale_fanout_unloaded_msgs_per_sec=round(base_rate))

        rss0 = _rss_bytes()
        stop = ct.c_int32(0)
        live = (ct.c_uint64 * 4)()
        herd_out = {}

        def herd():
            herd_out.update(native.loadgen_conn_scale(
                "127.0.0.1", server.port, n_real, burst=256,
                keepalive_s=20, sub_every=10, hold_ms=600_000,
                stop=stop, live=live))

        t_conn0 = time.time()
        ht = threading.Thread(target=herd, daemon=True)
        ht.start()
        deadline = time.time() + 240
        while time.time() < deadline and live[0] < n_real * 0.99:
            time.sleep(0.25)
        connected = int(live[0])
        storm_s = time.time() - t_conn0
        put("conn_scale", conn_scale_real_n=connected,
            conn_scale_connect_per_sec=round(connected /
                                             max(storm_s, 1e-9)))
        rss_resident = _rss_bytes()
        put("conn_scale",
            conn_scale_real_resident_bytes_per_conn=round(
                (rss_resident - rss0) / max(connected, 1)))
        # let the herd hibernate (park horizon 3s; pings ride the
        # parked fast path so the herd STAYS parked)
        t0 = time.time()
        while time.time() - t0 < 60:
            if server.fast_stats()["conns_parked"] >= connected * 0.9:
                break
            time.sleep(0.5)
        parked_events = server.fast_stats()["conns_parked"]
        _malloc_trim()
        rss_parked = _rss_bytes()
        put("conn_scale", conn_scale_real_parked_events=parked_events,
            conn_scale_real_parked_rss_delta_bytes_per_conn=round(
                (rss_parked - rss0) / max(connected, 1)))
        # fan-out with >= 99% of conns idle-parked (same best-of-N)
        loaded_rate = fan_best()
        ratio = loaded_rate / max(base_rate, 1e-9)
        stop.value = 1
        ht.join(timeout=60)
        p99_ms = herd_out.get("ping_p99_ns", 0) / 1e6
        put("conn_scale",
            conn_scale_fanout_with_herd_msgs_per_sec=round(loaded_rate),
            conn_scale_fanout_ratio_real_sockets=round(ratio, 3),
            conn_scale_ping_p50_ms=round(
                herd_out.get("ping_p50_ns", 0) / 1e6, 2),
            conn_scale_ping_p99_ms=round(p99_ms, 2),
            conn_scale_pings=int(herd_out.get("pings", 0)),
            conn_scale_herd_errors=int(herd_out.get("errors", 0)),
            conn_scale_broker_closes=int(
                herd_out.get("broker_closes", 0)),
            conn_scale_keepalive_honored=bool(
                p99_ms < 1000.0
                and herd_out.get("broker_closes", 1) == 0),
            conn_scale_parked_pings=server.fast_stats()["parked_pings"])
        # the PLANE's own fan-out tax, isolated: a 100k synthetic herd
        # parks on the SAME broker (no kernel sockets, no Python conn
        # objects — exactly the structures this PR added) and the
        # fan-out reruns. The real-socket ratio above additionally
        # carries the herd client sharing this 1-core box and the
        # kernel-socket + Python-object footprint (the documented
        # carried edge); the gate isolates the new subsystem.
        t0 = time.time()
        while time.time() - t0 < 20 and len(server.conns) > 16:
            time.sleep(0.25)   # real herd teardown drains
        base2 = fan_best()
        server.hosts[0].synth_conns(100_000, keepalive_ms=0,
                                    sub_every=10,
                                    topic_prefix="synthherd")
        t0 = time.time()
        want = server.fast_stats()["conns_parked"] + 99_000
        while time.time() - t0 < 60:
            if server.fast_stats()["conns_parked"] >= want:
                break
            time.sleep(0.25)
        loaded2 = fan_best()
        ratio2 = loaded2 / max(base2, 1e-9)
        put("conn_scale",
            conn_scale_synth_herd_on_broker=100_000,
            conn_scale_fanout_unloaded2_msgs_per_sec=round(base2),
            conn_scale_fanout_with_synth_herd_msgs_per_sec=round(
                loaded2),
            conn_scale_fanout_ratio=round(ratio2, 3),
            conn_scale_fanout_within_10pct=bool(ratio2 >= 0.9))
    finally:
        server.stop()

    # -- arm B: the 1M herd on a raw host -------------------------------
    host = native.NativeHost(port=0, max_size=4096)
    try:
        _malloc_trim()
        rss0 = _rss_bytes()
        chunk = 100_000
        t0 = time.time()
        done = 0
        while done < n_synth:
            host.synth_conns(min(chunk, n_synth - done),
                             keepalive_ms=3_600_000, sub_every=20,
                             topic_prefix="herd1m")
            done += chunk
            list(host.poll(0))
        cc = host.conn_counts()
        rss_resident = _rss_bytes()
        put("conn_scale", conn_scale_synth_n=int(cc["resident"]),
            conn_scale_synth_create_s=round(time.time() - t0, 1),
            conn_scale_synth_resident_bytes_per_conn=round(
                (rss_resident - rss0) / max(cc["resident"], 1)))
        # the old housekeep shape: one conn_idle_ms probe per conn per
        # tick — measure a 100k slice and project to the full herd
        t0 = time.time()
        probe_n = 100_000
        for cid in range(1, probe_n + 1):
            host.conn_idle_ms(cid)
        sweep_ms = (time.time() - t0) * 1000 * (n_synth / probe_n)
        # hibernate the herd through the real park machinery
        host.set_park(True, park_after_ms=100)
        t0 = time.time()
        while time.time() - t0 < 300:
            list(host.poll(0))
            cc = host.conn_counts()
            if cc["parked"] >= n_synth * 0.999:
                break
        park_s = time.time() - t0
        _malloc_trim()
        rss_parked = _rss_bytes()
        cc = host.conn_counts()
        # idle housekeep cost with the full herd parked + 1M armed
        # keepalive timers: the wheel pays O(expired)
        t0 = time.time()
        cycles = 200
        for _ in range(cycles):
            list(host.poll(0))
        cycle_us = (time.time() - t0) * 1e6 / cycles
        put("conn_scale",
            conn_scale_parked_n=int(cc["parked"]),
            conn_scale_park_drain_s=round(park_s, 1),
            conn_scale_parked_record_bytes_per_conn=round(
                cc["parked_bytes"] / max(cc["parked"], 1)),
            conn_scale_parked_rss_bytes_per_conn=round(
                (rss_parked - rss0) / max(cc["parked"], 1)),
            conn_scale_timers_armed=int(cc["timers_armed"]),
            conn_scale_idle_cycle_us_at_1m_parked=round(cycle_us, 1),
            conn_scale_old_sweep_projection_ms=round(sweep_ms, 1),
            # the acceptance claim: housekeep no longer scales O(N)
            # with parked conns — an idle cycle over the parked
            # million costs ~3 orders less than one old-style sweep
            conn_scale_housekeep_o_expired=bool(
                cycle_us / 1000.0 < sweep_ms / 100.0))
    finally:
        host.destroy()


# ---------------------------------------------------------------------------
# section: fault_overhead (faultline disarmed cost; CPU by design)
# ---------------------------------------------------------------------------

_FAULT_ARM_SRC = r"""
import sys
sys.path.insert(0, %(repo)r)
from emqx_tpu import native
from emqx_tpu.app import BrokerApp
from emqx_tpu.broker.native_server import NativeBrokerServer

server = NativeBrokerServer(port=0, app=BrokerApp(),
                            session_opts={"max_inflight": 1024})
server.start()
r = native.loadgen_run("127.0.0.1", server.port, n_subs=8, n_pubs=8,
                       msgs_per_pub=%(n_msg)d, qos=0, payload_len=16)
print("RATE", r["received"] / max(r["wall_ns"] / 1e9, 1e-9), flush=True)
server.stop()
"""


def sec_fault_overhead() -> None:
    """ISSUE 11 acceptance: disarmed fault sites are FREE — the qos0
    fan-out with the faultline-compiled binary lands within the 2%
    noise budget of a -DEMQX_NO_FAULTLINE build (every site compiled
    out; EMQX_NATIVE_NOFAULT=1 selects it). Each arm runs the broker +
    loadgen in a SUBPROCESS so the two .so variants never share a
    process; interleaved best-of-N with alternating pair order (the
    round-13 warm-box discipline)."""
    import subprocess as sp

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return
    repo = os.path.dirname(os.path.abspath(__file__))
    n_msg = int(os.environ.get("BENCH_FAULT_MSGS", 40000))
    reps = int(os.environ.get("BENCH_FAULT_REPS", 3))
    src = _FAULT_ARM_SRC % {"repo": repo, "n_msg": n_msg}
    best = {"faultline": 0.0, "nofault": 0.0}
    for rep in range(reps):
        arms = (("faultline", "nofault") if rep % 2 == 0
                else ("nofault", "faultline"))
        for arm in arms:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            if arm == "nofault":
                env["EMQX_NATIVE_NOFAULT"] = "1"
            else:
                env.pop("EMQX_NATIVE_NOFAULT", None)
            p = sp.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=300)
            rate = 0.0
            for line in p.stdout.splitlines():
                if line.startswith("RATE "):
                    rate = float(line.split()[1])
            if rate <= 0:
                log(f"fault_overhead rep{rep} {arm}: FAILED "
                    f"{p.stderr[-500:]}")
                continue
            best[arm] = max(best[arm], rate)
            log(f"fault_overhead rep{rep} {arm}: {rate:,.0f} msg/s")
    if best["faultline"] <= 0 or best["nofault"] <= 0:
        # a dead arm must never read as a budget pass: with the
        # baseline at 0 the overhead goes hugely negative and
        # "< 2%" would be a false green on a run that measured nothing
        log(f"fault_overhead: arm(s) produced no rate "
            f"(faultline={best['faultline']:,.0f} "
            f"compiled-out={best['nofault']:,.0f}) — no verdict")
        put("fault_overhead",
            qos0_msgs_per_sec_faultline=round(best["faultline"]),
            qos0_msgs_per_sec_compiled_out=round(best["nofault"]),
            within_2pct_budget=False, failed_arm=True)
        return
    overhead = 1.0 - best["faultline"] / best["nofault"]
    log(f"fault_overhead: faultline={best['faultline']:,.0f} "
        f"compiled-out={best['nofault']:,.0f} msg/s  "
        f"overhead={overhead * 100:.2f}% "
        f"({'within' if overhead < 0.02 else 'OVER'} the 2% budget)")
    put("fault_overhead",
        qos0_msgs_per_sec_faultline=round(best["faultline"]),
        qos0_msgs_per_sec_compiled_out=round(best["nofault"]),
        overhead_frac=round(overhead, 4),
        within_2pct_budget=bool(overhead < 0.02))


# ---------------------------------------------------------------------------
# raw-socket MQTT codec shared by the trunk/durable sections (one copy:
# a framing fix must not have to land twice)
# ---------------------------------------------------------------------------

def mqtt_connect(cid, clean=True):
    import struct
    flags = 0x02 if clean else 0x00
    vh = (b"\x00\x04MQTT\x04" + bytes([flags]) + b"\x00\x3c"
          + struct.pack(">H", len(cid)) + cid)
    return bytes([0x10, len(vh)]) + vh


def mqtt_subscribe(pid, topic, qos=0):
    import struct
    body = struct.pack(">H", pid) + struct.pack(">H", len(topic)) \
        + topic + bytes([qos])
    return bytes([0x82, len(body)]) + body


def mqtt_publish(topic, payload, qos=0, pid=0):
    import struct
    body = struct.pack(">H", len(topic)) + topic
    if qos:
        body += struct.pack(">H", pid)
    body += payload
    head = bytes([0x30 | (qos << 1)])
    remaining = len(body)
    var = b""
    while True:
        b7 = remaining & 0x7F
        remaining >>= 7
        var += bytes([b7 | (0x80 if remaining else 0)])
        if not remaining:
            break
    return head + var + body


def count_publishes(buf, counts):
    """Consume whole frames from buf, counting PUBLISHes; returns the
    unconsumed tail."""
    pos = 0
    while True:
        if len(buf) - pos < 2:
            break
        rl = 0
        shift = 0
        i = pos + 1
        ok = True
        while True:
            if i >= len(buf):
                ok = False
                break
            byte = buf[i]
            rl |= (byte & 0x7F) << shift
            shift += 7
            i += 1
            if not byte & 0x80:
                break
        if not ok or len(buf) - i < rl:
            break
        if buf[pos] >> 4 == 3:
            counts[0] += 1
        pos = i + rl
    return buf[pos:]


def publish_drainer(sock, counts, stop):
    """Count inbound PUBLISHes until stop. select-based on purpose: the
    durable replay leg shares the PUBLISHER's socket with the main
    thread's sendall loop, and a socket-level settimeout would apply to
    send too — a >200ms fsync stall mid-blast would then raise
    TimeoutError out of sendall and kill the whole section."""
    import select
    buf = b""
    while not stop.is_set():
        try:
            r, _, _ = select.select([sock], [], [], 0.2)
            if not r:
                continue
            chunk = sock.recv(1 << 16)
        except (OSError, ValueError):
            return
        if not chunk:
            return
        buf = count_publishes(buf + chunk, counts)


# ---------------------------------------------------------------------------
# section: trunk (cross-node forwarding on the native plane; CPU by design)
# ---------------------------------------------------------------------------

def sec_trunk() -> None:
    """ISSUE 4 acceptance: a two-node loopback pair forwarding QoS0
    cross-node over the NATIVE trunk must run >= 10x the Python gen_rpc
    lane (TcpTransport casts through both nodes' Python planes — the
    lane every cross-node leg rode before this round). Same driver both
    arms: raw-socket publisher on node A, raw-socket subscriber on node
    B, the cluster plane replicating the route; the arms differ only by
    attach_native (trunk adverts on hello/ping)."""
    import socket
    import threading

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.cluster.node import ClusterNode
    from emqx_tpu.cluster.transport import TcpTransport

    def build_pair(trunk: bool, suffix: str):
        ta = TcpTransport(f"bA{suffix}")
        tb = TcpTransport(f"bB{suffix}")
        ta.add_peer(tb.node, tb.host, tb.port)
        tb.add_peer(ta.node, ta.host, ta.port)
        na = ClusterNode(ta.node, ta)
        nb = ClusterNode(tb.node, tb)
        sa = NativeBrokerServer(port=0, app=na.app,
                                trunk_port=0 if trunk else None)
        sb = NativeBrokerServer(port=0, app=nb.app,
                                trunk_port=0 if trunk else None)
        if trunk:
            na.attach_native(sa)
            nb.attach_native(sb)
        sa.start()
        sb.start()
        nb.join([na.name])
        return na, nb, sa, sb

    def drive(trunk: bool, suffix: str, n_msg: int, deadline_s: float):
        na, nb, sa, sb = build_pair(trunk, suffix)
        try:
            sub = socket.create_connection(("127.0.0.1", sb.port))
            sub.sendall(mqtt_connect(b"bsub") + mqtt_subscribe(1, b"bt/x"))
            pub = socket.create_connection(("127.0.0.1", sa.port))
            pub.sendall(mqtt_connect(b"bpub"))
            time.sleep(0.3)
            na.flush()
            nb.flush()
            if trunk:
                t0 = time.time()
                while (not sa.trunk_peer_status().get(nb.name)
                       and time.time() - t0 < 10):
                    time.sleep(0.05)
                assert sa.trunk_peer_status().get(nb.name), "trunk not up"
            counts = [0]
            stop = threading.Event()
            dt = threading.Thread(target=publish_drainer,
                                  args=(sub, counts, stop), daemon=True)
            dt.start()
            # warm leg earns the permit through the Python lane
            pub.sendall(mqtt_publish(b"bt/x", b"warm-up-00000"))
            t0 = time.time()
            while counts[0] < 1 and time.time() - t0 < 15:
                time.sleep(0.05)
            time.sleep(0.6)     # permit grants on an idle poll step
            frame = mqtt_publish(b"bt/x", b"x" * 16)
            blob = frame * 256
            sent = 0
            t0 = time.time()
            while sent < n_msg and time.time() - t0 < deadline_s:
                pub.sendall(blob)
                sent += 256
            t_sent = time.time()
            deadline = t_sent + max(15.0, deadline_s / 2)
            last = -1
            while counts[0] < sent + 1 and time.time() < deadline:
                if counts[0] != last:
                    last = counts[0]
                time.sleep(0.05)
            wall = time.time() - t0
            received = counts[0] - 1      # minus the warm leg
            rate = received / max(wall, 1e-9)
            # windowed cross-node latency: W outstanding, p99 of the
            # per-window round trip (send last byte -> all W received)
            lats = []
            W = 64
            for _ in range(40):
                base = counts[0]
                lt0 = time.time()
                pub.sendall(frame * W)
                while counts[0] < base + W and time.time() - lt0 < 5:
                    time.sleep(0)
                lats.append((time.time() - lt0) * 1000 / W)
            lats.sort()
            p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
            stop.set()
            dt.join(timeout=2)
            stats = sa.fast_stats()
            summ = sa.latency_summary() if trunk else {}
            for s in (pub, sub):
                try:
                    s.close()
                except OSError:
                    pass
            return rate, received, sent, p99, stats, summ
        finally:
            sa.stop()
            sb.stop()
            na.transport.close()
            nb.transport.close()

    n_py = int(os.environ.get("BENCH_TRUNK_PY_MSGS", 4096))
    n_tk = int(os.environ.get("BENCH_TRUNK_MSGS", 120000))

    py_rate, py_recv, py_sent, py_p99, py_stats, _ = drive(
        False, "p", n_py, 60.0)
    log(f"trunk BEFORE (python gen_rpc lane, qos0 cross-node): "
        f"{py_recv}/{py_sent} = {py_rate:,.0f} msg/s "
        f"p99/msg={py_p99:.3f}ms (trunk_out={py_stats['trunk_out']})")
    put("trunk", trunk_python_fwd_msgs_per_sec=round(py_rate),
        trunk_python_fwd_p99_ms=round(py_p99, 3))

    tk_rate, tk_recv, tk_sent, tk_p99, tk_stats, summ = drive(
        True, "t", n_tk, 90.0)
    ratio = tk_rate / max(py_rate, 1e-9)
    log(f"trunk AFTER (native trunk, qos0 cross-node): "
        f"{tk_recv}/{tk_sent} = {tk_rate:,.0f} msg/s "
        f"p99/msg={tk_p99:.3f}ms  ({ratio:,.1f}x the python lane"
        f"{'' if ratio >= 10 else ' — UNDER the 10x acceptance'}; "
        f"trunk_out={tk_stats['trunk_out']} "
        f"batches={tk_stats['trunk_batches_out']})")
    put("trunk",
        trunk_native_msgs_per_sec=round(tk_rate),
        trunk_native_p99_ms=round(tk_p99, 3),
        trunk_vs_python=round(ratio, 2),
        trunk_10x_acceptance=bool(ratio >= 10))
    # broker-side trunk-stage percentiles (enqueue->peer-ack RTT in us;
    # batch occupancy's "us" axis is really an entry count / 1000 — the
    # one count-valued stage, host.cc kHistTrunkBatchN)
    for stage in ("trunk_rtt", "trunk_batch_n"):
        if stage in summ:
            s = summ[stage]
            log(f"broker-side {stage}: p50={s['p50_us']:.1f} "
                f"p99={s['p99_us']:.1f} (n={s['count']})")
            put("trunk", **{
                f"trunk_broker_{stage}_p50_us": round(s["p50_us"], 1),
                f"trunk_broker_{stage}_p99_us": round(s["p99_us"], 1)})


# ---------------------------------------------------------------------------
# section: durable (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------

def sec_durable() -> None:
    """ISSUE 5 acceptance: with ONE persistent subscriber in a fan-out
    audience, fast-path throughput must be >= 10x the punt-everything
    behavior (pre-round-10, a single durable subscriber collapsed every
    matching publish onto the Python plane). Same driver both arms —
    raw-socket publisher + N fast subscribers + 1 persistent subscriber
    — differing only by the durable plane being attached. Plus the
    resume-replay drain rate (store -> native delivery machinery)."""
    import socket
    import tempfile
    import threading

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.session.persistent import MemStore

    def build(durable: bool):
        app = BrokerApp(persistent_store=MemStore())
        server = NativeBrokerServer(
            port=0, app=app, durable=durable,
            durable_dir=tempfile.mkdtemp(prefix="emqx_dur_")
            if durable else None)
        server.start()
        return server

    N_FAST = int(os.environ.get("BENCH_DURABLE_FANOUT", 4))

    def drive(durable: bool, n_msg: int, deadline_s: float):
        server = build(durable)
        socks, threads, stop = [], [], threading.Event()
        counts = [[0] for _ in range(N_FAST)]
        try:
            for i in range(N_FAST):
                s = socket.create_connection(("127.0.0.1", server.port))
                s.sendall(mqtt_connect(b"df%d" % i)
                          + mqtt_subscribe(1, b"du/t"))
                socks.append(s)
                t = threading.Thread(target=publish_drainer,
                                     args=(s, counts[i], stop),
                                     daemon=True)
                t.start()
                threads.append(t)
            ps = socket.create_connection(("127.0.0.1", server.port))
            ps.sendall(mqtt_connect(b"dps", clean=False)
                       + mqtt_subscribe(1, b"du/t", qos=1))
            pcount = [0]
            pt = threading.Thread(target=publish_drainer,
                                  args=(ps, pcount, stop), daemon=True)
            pt.start()
            pub = socket.create_connection(("127.0.0.1", server.port))
            pub.sendall(mqtt_connect(b"dpub"))
            time.sleep(0.3)
            # warm leg earns the permit through the Python plane
            pub.sendall(mqtt_publish(b"du/t", b"warm-000"))
            t0 = time.time()
            while counts[0][0] < 1 and time.time() - t0 < 15:
                time.sleep(0.05)
            time.sleep(0.8)     # permit grants on an idle poll step
            blob = mqtt_publish(b"du/t", b"x" * 16) * 256
            sent = 0
            t0 = time.time()
            while sent < n_msg and time.time() - t0 < deadline_s:
                pub.sendall(blob)
                sent += 256
            deadline = time.time() + max(15.0, deadline_s / 2)
            while counts[0][0] < sent + 1 and time.time() < deadline:
                time.sleep(0.05)
            wall = time.time() - t0
            received = counts[0][0] - 1          # minus the warm leg
            rate = received / max(wall, 1e-9)
            st = server.fast_stats()
            return rate, received, sent, st, server, socks + [ps, pub], \
                stop, threads + [pt]
        except Exception:
            stop.set()
            server.stop()
            raise

    def teardown(server, socks, stop, threads):
        stop.set()
        for t in threads:
            t.join(timeout=2)
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.stop()

    n_before = int(os.environ.get("BENCH_DURABLE_PY_MSGS", 4096))
    n_after = int(os.environ.get("BENCH_DURABLE_MSGS", 120000))

    rate0, recv0, sent0, st0, srv0, socks0, stop0, th0 = drive(
        False, n_before, 45.0)
    log(f"durable BEFORE (punt-everything: 1 persistent sub among "
        f"{N_FAST} fast subs, qos0): {recv0}/{sent0} = {rate0:,.0f} "
        f"msg/s (punts={st0['punts']}, durable_in={st0['durable_in']})")
    teardown(srv0, socks0, stop0, th0)
    put("durable", durable_fanout_before_msgs_per_sec=round(rate0),
        durable_fanout_n_fast=N_FAST)

    rate1, recv1, sent1, st1, srv1, socks1, stop1, th1 = drive(
        True, n_after, 60.0)
    ratio = rate1 / max(rate0, 1e-9)
    log(f"durable AFTER (native durable plane): {recv1}/{sent1} = "
        f"{rate1:,.0f} msg/s ({ratio:,.1f}x the punt path"
        f"{'' if ratio >= 10 else ' — UNDER the 10x acceptance'}; "
        f"durable_in={st1['durable_in']} punts={st1['punts']} "
        f"store_appends={st1['store_appends']})")
    put("durable",
        durable_fanout_after_msgs_per_sec=round(rate1),
        durable_vs_punt=round(ratio, 2),
        durable_10x_acceptance=bool(ratio >= 10))
    put_broker_hists("durable", srv1, "durable")
    teardown(srv1, socks1, stop1, th1)

    # -- resume-replay drain rate -------------------------------------------
    server = build(True)
    try:
        ps = socket.create_connection(("127.0.0.1", server.port))
        ps.sendall(mqtt_connect(b"drp", clean=False)
                   + mqtt_subscribe(1, b"dr/t", qos=1))
        time.sleep(0.4)
        ps.sendall(b"\xe0\x00")          # DISCONNECT: offline, session kept
        ps.close()
        pub = socket.create_connection(("127.0.0.1", server.port))
        pub.sendall(mqtt_connect(b"drpub"))
        stop = threading.Event()
        acks = [0]
        at = threading.Thread(target=publish_drainer, args=(pub, acks, stop),
                              daemon=True)
        at.start()
        time.sleep(0.3)
        pub.sendall(mqtt_publish(b"dr/t", b"warm", qos=1, pid=1))
        time.sleep(0.8)                  # permit grant window
        n_replay = int(os.environ.get("BENCH_DURABLE_REPLAY_MSGS", 20000))
        sent = 0
        blob = b"".join(mqtt_publish(b"dr/t", b"y" * 16, qos=1,
                                     pid=1 + (k % 60000))
                        for k in range(256))
        while sent < n_replay:
            pub.sendall(blob)
            sent += 256
        tok = server._durable_tokens.get("drp")
        t0 = time.time()
        while (tok is None or server._durable_store.pending(tok)
               < sent) and time.time() - t0 < 30:
            time.sleep(0.1)
            tok = server._durable_tokens.get("drp")
        stored = server._durable_store.pending(tok) if tok else 0
        # resume: the replay rides session.deliver -> host.send
        ps2 = socket.create_connection(("127.0.0.1", server.port))
        rcount = [0]
        rt = threading.Thread(target=publish_drainer, args=(ps2, rcount, stop),
                              daemon=True)
        t0 = time.time()
        ps2.sendall(mqtt_connect(b"drp", clean=False))
        rt.start()
        deadline = t0 + 60
        # qos1 replay throttles on the session window without acks; the
        # drain counts deliveries, acking is out of scope — measure the
        # first-window burst plus stored drain via the store gauge
        while (tok and server._durable_store.pending(tok) > 0
               and time.time() < deadline):
            time.sleep(0.05)
        drain_wall = time.time() - t0
        drained = stored - (server._durable_store.pending(tok)
                            if tok else 0)
        drate = drained / max(drain_wall, 1e-9)
        time.sleep(1.0)   # let the first-window deliveries hit the wire
        log(f"durable replay: {stored} stored, {drained} drained in "
            f"{drain_wall:.2f}s = {drate:,.0f} msg/s "
            f"(first-window deliveries on the wire: {rcount[0]}; the "
            f"rest ride the session mqueue/window as the client acks)")
        put("durable",
            durable_replay_stored=stored,
            durable_replay_drain_msgs_per_sec=round(drate))
        put_broker_hists("durable", server, "durable_replay")
        stop.set()
        for s in (pub, ps2):
            try:
                s.close()
            except OSError:
                pass
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# section: mixed (edge-gateway plane: MQTT-SN + retained; CPU by design)
# ---------------------------------------------------------------------------

def sec_mixed() -> None:
    """ISSUE 6 acceptance: (a) native-SN publish throughput >= 10x the
    asyncio gateway/mqttsn.py path on the same box, (b) retained COLD
    delivery on the native snapshot >= 10x the Python retain-lookup
    path, with per-stage broker histograms (sn_ingest, retain_deliver)
    recorded; plus the mixed-protocol blast (TCP+WS+SN publishers on
    ONE broker, topic/cid spaces salted apart so the planes share the
    match table without cross-plane fan-out)."""
    import asyncio
    import select
    import socket
    import threading

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.broker.server import BrokerServer
    from emqx_tpu.core.message import Message
    from emqx_tpu.gateway import mqttsn as SN

    n_before = int(os.environ.get("BENCH_SN_BEFORE_MSGS", 1000))
    n_blast = int(os.environ.get("BENCH_SN_BLAST_MSGS", 20000))
    n_mixed = int(os.environ.get("BENCH_MIXED_MSGS", 12000))
    n_ret = int(os.environ.get("BENCH_RETAIN_TOPICS", 2000))

    # -- before: asyncio SN gateway (gateway/mqttsn.py), SAME loadgen -------
    # the SN loadgen speaks the shared sn.h codec against either plane,
    # so both arms see identical wire traffic
    gw_state: dict = {}
    gw_stop = threading.Event()
    gw_ready = threading.Event()

    def gw_main():
        async def run_gw():
            app = BrokerApp()
            gw = app.gateway.load(SN.MqttsnGateway(port=0))
            await gw.start_listeners()
            gw_state["port"] = gw.port
            gw_ready.set()
            while not gw_stop.is_set():
                await asyncio.sleep(0.05)
            await gw.stop_listeners()
        asyncio.run(run_gw())

    th = threading.Thread(target=gw_main)
    th.start()
    assert gw_ready.wait(10), "asyncio SN gateway did not come up"
    try:
        before = native.loadgen_sn_run(
            "127.0.0.1", gw_state["port"], n_subs=4, n_pubs=4,
            msgs_per_pub=n_before, qos=0, payload_len=16,
            idle_timeout_ms=8000, window=256)
    finally:
        gw_stop.set()
        th.join()
    before_rate = before["received"] / max(before["wall_ns"] / 1e9, 1e-9)
    log(f"sn plane BEFORE (asyncio gateway/mqttsn.py, qos0 windowed): "
        f"{before['received']}/{before['sent']} = {before_rate:,.0f} msg/s")
    put("mixed", sn_asyncio_msgs_per_sec=round(before_rate))

    # -- after: native SN gateway (sn.h in the C++ host) --------------------
    server = NativeBrokerServer(port=0, app=BrokerApp(), ws_port=0,
                                sn_port=0,
                                session_opts={"max_inflight": 1024})
    server.start()
    try:
        # identical pacing to the BEFORE arm (window + idle timeout):
        # the ratio must measure the plane, not the window depth
        sn = native.loadgen_sn_run(
            "127.0.0.1", server.sn_port, n_subs=4, n_pubs=4,
            msgs_per_pub=n_blast, qos=0, payload_len=16,
            idle_timeout_ms=8000, window=256)
        sn_rate = sn["received"] / max(sn["wall_ns"] / 1e9, 1e-9)
        log(f"sn plane AFTER (native sn.h + fast path, qos0 windowed): "
            f"{sn['received']}/{sn['sent']} = {sn_rate:,.0f} msg/s  "
            f"({sn_rate / max(before_rate, 1):,.0f}x asyncio-sn)  "
            f"p99={sn['p99_ns'] / 1e6:.3f}ms")
        put("mixed",
            sn_native_msgs_per_sec=round(sn_rate),
            sn_native_p99_ms=round(sn["p99_ns"] / 1e6, 3),
            sn_vs_asyncio=round(sn_rate / max(before_rate, 1), 1))

        # qos1 rides the native ack plane (inflight bitmaps + SN PUBACK)
        q1 = native.loadgen_sn_run(
            "127.0.0.1", server.sn_port, n_subs=4, n_pubs=4,
            msgs_per_pub=n_blast // 4, qos=1, payload_len=16, window=512)
        q1_rate = q1["received"] / max(q1["wall_ns"] / 1e9, 1e-9)
        log(f"sn plane qos1 (windowed 512): {q1_rate:,.0f} msg/s "
            f"acks={q1['acks']} p99={q1['p99_ns'] / 1e6:.3f}ms")
        put("mixed",
            sn_native_qos1_msgs_per_sec=round(q1_rate),
            sn_native_qos1_p99_ms=round(q1["p99_ns"] / 1e6, 3))

        # -- mixed-protocol blast: TCP + WS + SN fleets on ONE broker -------
        res: dict = {}

        def tcp_arm():
            res["tcp"] = native.loadgen_run(
                "127.0.0.1", server.port, n_subs=4, n_pubs=4,
                msgs_per_pub=n_mixed, qos=0, payload_len=16)

        def ws_arm():
            res["ws"] = native.loadgen_run(
                "127.0.0.1", server.ws_port, n_subs=4, n_pubs=4,
                msgs_per_pub=n_mixed, qos=0, payload_len=16, ws=True,
                salt=100)

        def sn_arm():
            res["sn"] = native.loadgen_sn_run(
                "127.0.0.1", server.sn_port, n_subs=4, n_pubs=4,
                msgs_per_pub=n_mixed, qos=0, payload_len=16)

        arms = [threading.Thread(target=f)
                for f in (tcp_arm, ws_arm, sn_arm)]
        t0 = time.time()
        for a in arms:
            a.start()
        for a in arms:
            a.join()
        wall = time.time() - t0
        total = sum(r["received"] for r in res.values())
        per = {k: round(r["received"] / max(r["wall_ns"] / 1e9, 1e-9))
               for k, r in res.items()}
        log(f"mixed blast (TCP+WS+SN concurrent, qos0): "
            f"{total} delivered in {wall:.2f}s = {total / wall:,.0f} msg/s "
            f"aggregate  (tcp={per['tcp']:,} ws={per['ws']:,} "
            f"sn={per['sn']:,} msg/s)")
        put("mixed",
            mixed_total_msgs_per_sec=round(total / wall),
            mixed_tcp_msgs_per_sec=per["tcp"],
            mixed_ws_msgs_per_sec=per["ws"],
            mixed_sn_msgs_per_sec=per["sn"])
        # broker-side stages incl. sn_ingest (sampled SN decode+dispatch)
        put_broker_hists("mixed", server, "mixed_broker")
    finally:
        server.stop()

    # -- retained delivery: Python retain-lookup vs native snapshot ---------
    # identical measurement sink on both arms: a raw-socket subscriber
    # (the shared module codec) timing SUBSCRIBE -> n_ret-th retained
    # PUBLISH; cold = first wildcard subscribe on a fresh conn, warm =
    # repeat on another fresh conn
    def seed_retainer(app):
        for i in range(n_ret):
            app.retainer.store(Message(topic=f"bret/{i:05d}",
                                       payload=b"r" * 16, qos=0,
                                       flags={"retain": True}))

    def measure_retained(port, tag):
        s = socket.create_connection(("127.0.0.1", port))
        s.sendall(mqtt_connect(b"ret-" + tag))
        got = b""
        while len(got) < 4:                    # CONNACK
            got += s.recv(4096)
        t0 = time.time()
        s.sendall(mqtt_subscribe(1, b"bret/#"))
        counts = [0]
        buf = got[4:]
        deadline = time.time() + 60
        while counts[0] < n_ret and time.time() < deadline:
            r, _, _ = select.select([s], [], [], 0.5)
            if not r:
                continue
            chunk = s.recv(1 << 16)
            if not chunk:
                break
            buf = count_publishes(buf + chunk, counts)
        wall = time.time() - t0
        s.close()
        return counts[0], wall

    # Python arm: asyncio BrokerServer, retainer.match + per-msg deliver
    py_state: dict = {}
    py_stop = threading.Event()
    py_ready = threading.Event()
    app_py = BrokerApp()
    seed_retainer(app_py)

    def py_main():
        async def run_py():
            srv = BrokerServer(port=0, app=app_py)
            await srv.start()
            py_state["port"] = srv.port
            py_ready.set()
            while not py_stop.is_set():
                await asyncio.sleep(0.05)
            await srv.stop()
        asyncio.run(run_py())

    th = threading.Thread(target=py_main)
    th.start()
    assert py_ready.wait(10), "asyncio broker did not come up"
    try:
        py_cold_n, py_cold_wall = measure_retained(py_state["port"], b"c1")
        py_warm_n, py_warm_wall = measure_retained(py_state["port"], b"c2")
    finally:
        py_stop.set()
        th.join()
    py_cold = py_cold_n / max(py_cold_wall, 1e-9)
    py_warm = py_warm_n / max(py_warm_wall, 1e-9)
    log(f"retained BEFORE (python retain-lookup, {n_ret} topics): "
        f"cold {py_cold_n} in {py_cold_wall:.3f}s = {py_cold:,.0f} msg/s, "
        f"warm {py_warm:,.0f} msg/s")
    put("mixed",
        retain_py_cold_msgs_per_sec=round(py_cold),
        retain_py_warm_msgs_per_sec=round(py_warm))

    # native arm: the retainer mirror installs the host-side snapshot
    # at boot; SUBSCRIBE-triggered delivery resolves below the GIL
    app_nat = BrokerApp()
    seed_retainer(app_nat)
    srv_ret = NativeBrokerServer(port=0, app=app_nat,
                                 session_opts={"max_inflight": 1024})
    srv_ret.start()
    try:
        nat_cold_n, nat_cold_wall = measure_retained(srv_ret.port, b"n1")
        nat_warm_n, nat_warm_wall = measure_retained(srv_ret.port, b"n2")
        nat_cold = nat_cold_n / max(nat_cold_wall, 1e-9)
        nat_warm = nat_warm_n / max(nat_warm_wall, 1e-9)
        st = srv_ret.fast_stats()
        log(f"retained AFTER (native snapshot, {n_ret} topics): "
            f"cold {nat_cold_n} in {nat_cold_wall:.3f}s = "
            f"{nat_cold:,.0f} msg/s ({nat_cold / max(py_cold, 1):,.0f}x "
            f"python cold), warm {nat_warm:,.0f} msg/s  "
            f"retain_msgs_out={st['retain_msgs_out']}")
        put("mixed",
            retain_native_cold_msgs_per_sec=round(nat_cold),
            retain_native_warm_msgs_per_sec=round(nat_warm),
            retain_native_vs_py_cold=round(nat_cold / max(py_cold, 1), 1))
        # broker-side retain_deliver stage (one SUBSCRIBE's snapshot
        # match + encode + write batch)
        put_broker_hists("mixed", srv_ret, "retain_broker")
    finally:
        srv_ret.stop()


# ---------------------------------------------------------------------------
# section: e2e (full broker stack with the device router on path)
# ---------------------------------------------------------------------------

def sec_e2e() -> None:
    """End-to-end broker number (VERDICT r1 weak #1): real MQTT clients
    over TCP against the asyncio host with the device router on the
    serving path — msg/s and delivery p99 through the full stack
    (parse → channel FSM → pipeline → kernel → CM → socket).  This is
    the broker-level figure comparable to the reference's 1M msg/s
    cluster claim; the kernel number above is the routing-core ceiling."""
    import asyncio

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.config import Config
    from emqx_tpu.broker.server import BrokerServer
    from emqx_tpu.mqtt.client import MqttClient

    n_pub = int(os.environ.get("BENCH_E2E_PUBS", 16))
    n_sub = int(os.environ.get("BENCH_E2E_SUBS", 16))
    n_msg = int(os.environ.get("BENCH_E2E_MSGS", 250))  # per publisher
    n_rules = int(os.environ.get("BENCH_RULES", 1000))  # config 5

    conf = Config()
    conf.put("router.device.enable", True)
    conf.put("router.device.max_levels", 8)
    # throughput section: pin the knee to 0 so every batch rides the
    # kernel (round-comparable device numbers); the low-load probe
    # below switches to the adaptive policy it is measuring
    conf.put("router.device.min_batch", 0)
    app = BrokerApp.from_config(conf)

    # BASELINE config 5: rule-engine SQL topic filters co-batched with the
    # router match — every FROM filter rides the SAME kernel launch as
    # fan-out; per-publish rule lookup is O(matched), not O(rules)
    # (emqx_rule_engine.erl:198-205)
    rule_hits = [0]
    if n_rules:
        app.rules.register_action(
            "bench_sink", lambda cols, args: rule_hits.__setitem__(
                0, rule_hits[0] + 1))
        for r in range(n_rules):
            # a few rules match live bench traffic; the rest are realistic
            # dead weight over the same topic space
            filt = (f"bench/{r % max(1, n_sub)}/+" if r < 8
                    else f"rules/fleet{r}/+/telemetry")
            app.rules.create_rule(
                f"bench_rule_{r}", f'SELECT topic FROM "{filt}"',
                [{"function": "bench_sink", "args": {}}])

    async def run():
        server = BrokerServer(port=0, app=app)
        await server.start()
        subs = [MqttClient(port=server.port, clientid=f"s{i}")
                for i in range(n_sub)]
        pubs = [MqttClient(port=server.port, clientid=f"p{i}")
                for i in range(n_pub)]
        for i, s in enumerate(subs):
            await s.connect()
            await s.subscribe(f"bench/{i}/+", qos=0)
        for p in pubs:
            await p.connect()
        # warm every pow2 batch shape the pipeline can hit (64..batch_max)
        # off the clock — each fresh shape costs an XLA compile
        def warm_shapes():
            model = app.broker.model
            b = 64
            while b <= app.pipeline.max_batch:
                model.publish_batch(["bench/warmup/x"] * b)
                b *= 2
        await asyncio.to_thread(warm_shapes)
        await pubs[0].publish("bench/0/warm", b"w", qos=0)
        await subs[0].recv(timeout=30)

        recv_done = asyncio.Event()
        lat_ns: list[int] = []
        expected = n_pub * n_msg            # each lands on exactly 1 sub
        got = 0

        async def drain(s):
            nonlocal got
            while got < expected:
                try:
                    m = await s.recv(timeout=10)
                except asyncio.TimeoutError:
                    break
                lat_ns.append(time.perf_counter_ns()
                              - int(m.payload.decode()))
                got += 1
                if got >= expected:
                    recv_done.set()

        drains = [asyncio.create_task(drain(s)) for s in subs]

        async def blast(i, p):
            for j in range(n_msg):
                stamp = str(time.perf_counter_ns()).encode()
                await p.publish(f"bench/{(i + j) % n_sub}/m", stamp, qos=0)

        t0 = time.time()
        await asyncio.gather(*(blast(i, p) for i, p in enumerate(pubs)))
        try:
            await asyncio.wait_for(recv_done.wait(), timeout=60)
        except asyncio.TimeoutError:
            pass
        wall = time.time() - t0
        for d in drains:
            d.cancel()

        # low-load latency (VERDICT r3 #3 done-criterion): sequential
        # publishes trickle in as 1-message batches, which the pipeline's
        # knee policy answers from the host oracle — no device RTT
        app.pipeline.min_device_batch = -1   # the policy under test
        probe = MqttClient(port=server.port, clientid="lat-probe")
        await probe.connect()
        await probe.subscribe("bench/lat/x", qos=0)
        low = []
        for i in range(40):
            t0 = time.perf_counter_ns()
            await pubs[0].publish("bench/lat/x", b"x", qos=0)
            try:
                await probe.recv(timeout=10)
            except asyncio.TimeoutError:
                # one dropped probe must not discard the whole e2e
                # section's already-measured results
                log(f"low-load probe: recv timeout at sample {i}")
                break
            low.append((time.perf_counter_ns() - t0) / 1e6)
            await asyncio.sleep(0.01)
        low_a = np.array(low) if low else np.array([float("nan")])
        await probe.close()

        for c in subs + pubs:
            try:
                await c.disconnect()
            except Exception:
                pass
        await server.stop()
        lat_ms = np.array(lat_ns, float) / 1e6
        log(f"e2e broker: {got}/{expected} msgs in {wall:.2f}s = "
            f"{got / wall:,.0f} msg/s end-to-end "
            f"(pubs={n_pub} subs={n_sub} qos=0, device path, "
            f"kernel launches={app.broker.model.launch_count}, "
            f"rules={n_rules} co-batched, rule fires={rule_hits[0]})")
        put("e2e", e2e_msgs_per_sec=round(got / max(wall, 1e-9)))
        if len(lat_ms):
            log(f"e2e delivery latency ms: p50={np.percentile(lat_ms, 50):.2f} "
                f"p99={np.percentile(lat_ms, 99):.2f}")
            put("e2e",
                e2e_p50_ms=round(float(np.percentile(lat_ms, 50)), 2),
                e2e_p99_ms=round(float(np.percentile(lat_ms, 99)), 2))
        log(f"e2e LOW-LOAD latency ms (device on, knee="
            f"{app.pipeline.device_knee()}, host-bypassed batches="
            f"{app.pipeline.host_batches}): "
            f"p50={np.percentile(low_a, 50):.2f} "
            f"p99={np.percentile(low_a, 99):.2f}")
        put("e2e",
            e2e_lowload_p50_ms=round(float(np.percentile(low_a, 50)), 2),
            e2e_lowload_p99_ms=round(float(np.percentile(low_a, 99)), 2))

    asyncio.run(run())

    # -- device-path ceiling under native load ------------------------------
    # The same app (warmed model/pipeline) behind the C++ host with the
    # fast path OFF: every publish runs Channel.handle_in → pipeline →
    # kernel. This is the honest "Python FSM + device router" e2e bound
    # (the r3 famine was Python clients measuring themselves; the C++
    # loadgen removes that), and the gap to the fast-path number above
    # is the remaining host-plane work for future rounds.
    from emqx_tpu import native as _native

    if _native.available() and os.environ.get("BENCH_DEVICE_E2E", "1") != "0":
        from emqx_tpu.broker.native_server import NativeBrokerServer

        app.pipeline.min_device_batch = 0   # measure the KERNEL path,
        server = NativeBrokerServer(port=0, app=app, fast_path=False)
        server.start()                      # not the knee's host bypass
        try:
            res = _native.loadgen_run(
                "127.0.0.1", server.port, n_subs=8, n_pubs=8,
                msgs_per_pub=int(os.environ.get("BENCH_DEVICE_E2E_MSGS",
                                                1500)),
                qos=0, payload_len=16, window=2048, warmup=False)
            wall = res["wall_ns"] / 1e9
            rate = res["received"] / max(wall, 1e-9)
            log(f"device-path e2e (native load, fast path OFF, window "
                f"2048): {res['received']}/{res['sent']} = {rate:,.0f} "
                f"msg/s through channel FSM + pipeline + kernel "
                f"(launches={app.broker.model.launch_count})")
            put("e2e", e2e_device_path_msgs_per_sec=round(rate))
        except Exception as e:  # noqa: BLE001
            # a loadgen flake must not cost the whole artifact (every
            # earlier section's numbers stay in the partial file)
            log(f"device-path e2e section failed, skipping: {e}")
        finally:
            server.stop()

    if _native.available() and os.environ.get("BENCH_LANE", "1") != "0":
        bench_device_lane(app)


def bench_device_lane(app) -> None:
    """The one-path hot loop (VERDICT r4 #2 done-criterion): the C++
    data plane with the DEVICE doing the wildcard match — permitted
    publishes park in C++, topics batch through the RouterModel kernel,
    and the response fans out natively by exact filter lookup. The
    device table is padded to BENCH_LANE_FILTERS wildcard filters
    (synthetic dead weight that does not match the published topics —
    the emqx_broker_bench wildcard-dense-table shape) so the number
    demonstrates device matching at scale, not an 8-entry walk."""
    from emqx_tpu import native as _native
    from emqx_tpu.broker.native_server import NativeBrokerServer

    _require_tpu()
    n_filters = int(os.environ.get("BENCH_LANE_FILTERS", 100_000))
    msgs_per_pub = int(os.environ.get("BENCH_LANE_MSGS", 20_000))
    model = app.broker.model
    rng = np.random.default_rng(23)
    t0 = time.time()
    filters = build_filters(n_filters, rng)
    n_slots = model.n_sub_slots
    for i, f in enumerate(filters):
        model.subscribe(f, int(i % n_slots))
    model.refresh()
    log(f"lane: padded device table with {n_filters} filters in "
        f"{time.time()-t0:.1f}s")

    app.pipeline.min_device_batch = 0
    server = NativeBrokerServer(port=0, app=app, device_lane="on")
    server.start()
    try:
        res = _native.loadgen_run(
            "127.0.0.1", server.port, n_subs=8, n_pubs=8,
            msgs_per_pub=msgs_per_pub, qos=0, payload_len=16,
            window=int(os.environ.get("BENCH_LANE_WINDOW", 8192)))
        wall = res["wall_ns"] / 1e9
        rate = res["received"] / max(wall, 1e-9)
        st = server.fast_stats()
        log(f"lane e2e (C++ plane + device match @ {n_filters} filters, "
            f"windowed): {res['received']}/{res['sent']} = {rate:,.0f} "
            f"msg/s  lane_in={st['lane_in']} lane_out={st['lane_out']} "
            f"punts={st['lane_punts']} fallback={st['lane_fallback']} "
            f"p99={res['p99_ns'] / 1e6:.2f}ms")
        put("e2e",
            lane_msgs_per_sec=round(rate),
            lane_filters=n_filters,
            lane_out=st["lane_out"],
            lane_p99_ms=round(res["p99_ns"] / 1e6, 2))
        # broker-side stages: lane_dwell is THE number here (enqueue →
        # device verdict applied — the kernel round trip as the data
        # plane experiences it)
        summ = put_broker_hists("e2e", server, "lane_broker")
        if "lane_dwell" in summ:
            s = summ["lane_dwell"]
            log(f"broker-side lane_dwell: p50={s['p50_us']:.0f}us "
                f"p99={s['p99_us']:.0f}us (n={s['count']})")
    except Exception as e:  # noqa: BLE001
        log(f"lane e2e subsection failed, skipping: {e}")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def sec_shards() -> None:
    """ISSUE 7 acceptance: 2-shard qos0 fan-out >= 1.6x the 1-shard
    throughput on this box (4-shard recorded when >= 4 cores). Two
    shapes, both burst-into-buffers (publishers pre-serialize the whole
    burst and the broker's outbufs absorb delivery, so the measurement
    window contains ONLY broker-plane work — the thing shards scale —
    instead of driver recv() competing for the same cores):

    - ``fanout`` (the headline): per-publisher topics with the audience
      on the publisher's shard — the accept-sharding scale-out story,
      near-linear by construction;
    - ``cross`` (the ring): one shared topic, audience split across
      shards, ~50%% of deliveries ride the SPSC rings — records the
      crossing tax, the ring occupancy histogram (shard_ring_n) and the
      shard_ring_out/in/full counters.
    """
    import socket
    import threading

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer

    FAN = int(os.environ.get("BENCH_SHARD_FANOUT", 8))
    N_PUBS = 2
    K = int(os.environ.get("BENCH_SHARD_BURST", 120_000))
    FRAME_PAYLOAD = b"x" * 16

    def connect_on_shard(server, cid, want, bufs=8 << 20):
        """Raw conn placed on shard `want` (None = anywhere): each
        retry re-rolls the kernel's SO_REUSEPORT hash via a fresh
        ephemeral source port."""
        for _ in range(96):
            before = set(server.conns)
            s = socket.create_connection(("127.0.0.1", server.port))
            s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, bufs)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, bufs)
            s.sendall(mqtt_connect(cid))
            new = set()
            t0 = time.time()
            while not new and time.time() - t0 < 5:
                new = set(server.conns) - before
                if not new:
                    time.sleep(0.005)
            conn_id = new.pop()
            if want is None or native.shard_of(conn_id) == want:
                return s
            s.close()
            time.sleep(0.02)
        raise RuntimeError(f"cannot place {cid} on shard {want}")

    def drain_all(socks):
        for s in socks:
            s.setblocking(False)
            while True:
                try:
                    if not s.recv(1 << 18):
                        break
                except BlockingIOError:
                    break
                except OSError:
                    break
            s.setblocking(True)

    def drive(shards: int, cross: bool, reps: int = 3):
        server = NativeBrokerServer(port=0, app=BrokerApp(),
                                    shards=shards)
        server.start()
        time.sleep(0.3)
        subs, pubs, frames = [], [], []
        try:
            for p in range(N_PUBS):
                sh = (p % shards) if shards > 1 else None
                topic = b"fan/all" if cross else b"fan/%d" % p
                if not cross or p == 0:
                    for i in range(FAN):
                        ssh = (i % shards) if (cross and shards > 1) \
                            else sh
                        s = connect_on_shard(server, b"bs%d_%d" % (p, i),
                                             ssh)
                        s.sendall(mqtt_subscribe(1, topic))
                        subs.append(s)
                s = connect_on_shard(server, b"bp%d" % p, sh)
                frames.append(mqtt_publish(topic, FRAME_PAYLOAD))
                pubs.append(s)
            for s, f in zip(pubs, frames):
                s.sendall(f)           # slow leg earns the permit
            time.sleep(0.8)
            drain_all(subs)
            fan_per_pub = FAN          # both shapes: FAN subs per topic
            # burst-into-buffers bound: every sub's burst share must fit
            # rcvbuf + the host outbuf (kHighWater 4MB), or the arm
            # stalls on backpressure instead of measuring capacity. The
            # cross shape lands BOTH publishers' bursts on every sub.
            k = K if not cross else min(K, (3 << 20) // 19 // N_PUBS)
            best = 0.0
            for _ in range(reps):
                expect = fan_per_pub * k * N_PUBS
                st0 = server.fast_stats()
                t0 = time.time()
                bts = [threading.Thread(
                    target=lambda s=s, f=f: s.sendall(f * k),
                    daemon=True) for s, f in zip(pubs, frames)]
                for t in bts:
                    t.start()
                last, stall = -1, 0
                while True:
                    done = (server.fast_stats()["fast_out"]
                            - st0["fast_out"])
                    if done >= expect:
                        break
                    if done == last:
                        stall += 1
                        if stall > 800:
                            break
                    else:
                        stall, last = 0, done
                    time.sleep(0.005)
                wall = time.time() - t0
                st1 = server.fast_stats()
                best = max(best,
                           (st1["fast_out"] - st0["fast_out"]) / wall)
                for t in bts:
                    t.join(timeout=5)
                drain_all(subs)
                time.sleep(0.3)
            st = server.fast_stats()
            hists = server.latency_summary()
            shard_hists = server.shard_latency_summary()
            return best, st, hists, shard_hists
        finally:
            for s in subs + pubs:
                try:
                    s.close()
                except OSError:
                    pass
            server.stop()

    shard_counts = [1, 2]
    if (os.cpu_count() or 2) >= 4:
        shard_counts.append(4)
    rates = {}
    for shape in ("fanout", "cross"):
        cross = shape == "cross"
        for s in shard_counts:
            rate, st, hists, shard_hists = drive(s, cross)
            rates[(shape, s)] = rate
            log(f"shards/{shape} s={s}: {rate/1e6:.2f}M msg/s "
                f"ring_out={st['shard_ring_out']} "
                f"ring_full={st['shard_ring_full']}")
            kv = {f"shards_{shape}_{s}shard_msgs_per_sec": round(rate)}
            if cross and s > 1:
                kv.update({
                    f"shards_cross_{s}shard_ring_out":
                        st["shard_ring_out"],
                    f"shards_cross_{s}shard_ring_in":
                        st["shard_ring_in"],
                    f"shards_cross_{s}shard_ring_full":
                        st["shard_ring_full"],
                    f"shards_cross_{s}shard_punts": st["punts"],
                })
                occ = hists.get("shard_ring_n")
                if occ:
                    # count-valued stage (the trunk_batch_n
                    # convention): "p50_us" slots carry ENTRIES/batch
                    kv[f"shards_cross_{s}shard_ring_occupancy_p50"] = \
                        occ["p50_us"]
                    kv[f"shards_cross_{s}shard_ring_occupancy_p99"] = \
                        occ["p99_us"]
            # per-shard stage breakdown (ingress + flush per shard)
            for shard, stages in shard_hists.items():
                for stage in ("ingress_route", "route_flush"):
                    sm = stages.get(stage)
                    if sm:
                        kv[f"shards_{shape}_{s}shard_s{shard}_"
                           f"{stage}_p50_us"] = sm["p50_us"]
            put("shards", **kv)
    for shape in ("fanout", "cross"):
        base = rates.get((shape, 1), 0)
        for s in shard_counts[1:]:
            if base:
                put("shards", **{
                    f"shards_{shape}_speedup_{s}x":
                        round(rates[(shape, s)] / base, 2)})
    ok = (rates.get(("fanout", 2), 0)
          >= 1.6 * rates.get(("fanout", 1), float("inf")))
    put("shards", shards_accept_2x_fanout_ge_1_6x=bool(ok))


def sec_coap() -> None:
    """ISSUE 15 acceptance: native-CoAP publish throughput AND observe
    fan-out >= 10x the asyncio gateway/coap.py path on IDENTICAL wire
    traffic with IDENTICAL pacing (the SN gate shape: the same coap.h
    loadgen fleet drives both planes, windowed the same), with
    broker-side stage hists (coap_ingest, observe_notify) recorded."""
    import asyncio
    import threading

    from emqx_tpu import native

    if not native.available():
        log(f"native host unavailable, skipping: {native.build_error()}")
        return

    from emqx_tpu.app import BrokerApp
    from emqx_tpu.broker.native_server import NativeBrokerServer
    from emqx_tpu.gateway import coap as COAP

    n_before = int(os.environ.get("BENCH_COAP_BEFORE_MSGS", 1000))
    n_blast = int(os.environ.get("BENCH_COAP_BLAST_MSGS", 20000))
    n_fan = int(os.environ.get("BENCH_COAP_FANOUT_MSGS", 16000))

    def run_asyncio_arm(fn):
        """One measurement against a fresh asyncio CoapGateway."""
        state: dict = {}
        stop = threading.Event()
        ready = threading.Event()

        def gw_main():
            async def run_gw():
                app = BrokerApp()
                gw = app.gateway.load(COAP.CoapGateway(port=0))
                await gw.start_listeners()
                state["port"] = gw.port
                ready.set()
                while not stop.is_set():
                    await asyncio.sleep(0.05)
                await gw.stop_listeners()
            asyncio.run(run_gw())

        th = threading.Thread(target=gw_main)
        th.start()
        assert ready.wait(10), "asyncio CoAP gateway did not come up"
        try:
            return fn(state["port"])
        finally:
            stop.set()
            th.join()

    # -- before: asyncio gateway/coap.py, the SAME loadgen fleet ------------
    before = run_asyncio_arm(lambda port: native.loadgen_coap_run(
        "127.0.0.1", port, n_subs=4, n_pubs=4, msgs_per_pub=n_before,
        qos=0, payload_len=16, idle_timeout_ms=8000, window=256))
    before_rate = before["received"] / max(before["wall_ns"] / 1e9, 1e-9)
    log(f"coap plane BEFORE (asyncio gateway/coap.py, NON windowed): "
        f"{before['received']}/{before['sent']} = "
        f"{before_rate:,.0f} msg/s")
    put("coap", coap_asyncio_msgs_per_sec=round(before_rate))


    # -- after: the native CoAP plane (coap.h in the C++ host) --------------
    server = NativeBrokerServer(port=0, app=BrokerApp(), coap_port=0,
                                session_opts={"max_inflight": 1024})
    server.start()
    try:
        # identical pacing to the BEFORE arm (window + idle timeout):
        # the ratio must measure the plane, not the window depth
        after = native.loadgen_coap_run(
            "127.0.0.1", server.coap_port, n_subs=4, n_pubs=4,
            msgs_per_pub=n_blast, qos=0, payload_len=16,
            idle_timeout_ms=8000, window=256)
        after_rate = after["received"] / max(after["wall_ns"] / 1e9, 1e-9)
        log(f"coap plane AFTER (native coap.h + fast path, NON "
            f"windowed): {after['received']}/{after['sent']} = "
            f"{after_rate:,.0f} msg/s  "
            f"({after_rate / max(before_rate, 1):,.0f}x asyncio-coap)  "
            f"p99={after['p99_ns'] / 1e6:.3f}ms")
        put("coap",
            coap_native_msgs_per_sec=round(after_rate),
            coap_native_p99_ms=round(after["p99_ns"] / 1e6, 3),
            coap_vs_asyncio=round(after_rate / max(before_rate, 1), 1),
            coap_pub_10x_gate=bool(
                after_rate >= 10 * max(before_rate, 1)))

        # qos1: CON publishes gated on the native ack plane
        q1 = native.loadgen_coap_run(
            "127.0.0.1", server.coap_port, n_subs=4, n_pubs=4,
            msgs_per_pub=n_blast // 4, qos=1, payload_len=16,
            window=256)
        q1_rate = q1["received"] / max(q1["wall_ns"] / 1e9, 1e-9)
        log(f"coap plane qos1 (CON windowed 256): {q1_rate:,.0f} msg/s "
            f"acks={q1['acks']} p99={q1['p99_ns'] / 1e6:.3f}ms")
        put("coap",
            coap_native_qos1_msgs_per_sec=round(q1_rate),
            coap_native_qos1_p99_ms=round(q1["p99_ns"] / 1e6, 3))

        # observe fan-out: 8 observers on ONE topic, identical shape
        # on both planes. Interleaved best-of-3 with the pair order
        # ALTERNATED per rep (the observe_overhead discipline): this
        # 1-core box's run-to-run drift swamps a single-shot ratio.
        def native_fan_arm():
            return native.loadgen_coap_run(
                "127.0.0.1", server.coap_port, n_subs=8, n_pubs=1,
                msgs_per_pub=max(n_fan // 8, 200), qos=0,
                payload_len=16, idle_timeout_ms=8000, window=512,
                fanout=True)

        def asyncio_fan_arm():
            return run_asyncio_arm(lambda port: native.loadgen_coap_run(
                "127.0.0.1", port, n_subs=8, n_pubs=1,
                msgs_per_pub=max(n_fan // 8, 200), qos=0,
                payload_len=16, idle_timeout_ms=8000, window=512,
                fanout=True))

        def rate_of(r):
            return r["received"] / max(r["wall_ns"] / 1e9, 1e-9)

        fan_rate = bf_rate = 0.0
        for rep in range(3):
            arms = ([asyncio_fan_arm, native_fan_arm] if rep % 2 == 0
                    else [native_fan_arm, asyncio_fan_arm])
            for arm in arms:
                r = rate_of(arm())
                if arm is native_fan_arm:
                    fan_rate = max(fan_rate, r)
                else:
                    bf_rate = max(bf_rate, r)
        log(f"coap observe fan-out (8 observers/1 topic, best-of-3 "
            f"interleaved): native {fan_rate:,.0f} notify/s vs asyncio "
            f"{bf_rate:,.0f} notify/s "
            f"({fan_rate / max(bf_rate, 1):,.0f}x)")
        put("coap",
            coap_asyncio_fanout_notifies_per_sec=round(bf_rate),
            coap_native_fanout_notifies_per_sec=round(fan_rate),
            coap_fanout_vs_asyncio=round(fan_rate / max(bf_rate, 1), 1),
            coap_fanout_10x_gate=bool(fan_rate >= 10 * max(bf_rate, 1)))
        # broker-side stages incl. coap_ingest + observe_notify
        put_broker_hists("coap", server, "coap_broker")
        st = server.host.stats()
        put("coap", coap_in=st["coap_in"], coap_punts=st["coap_punts"],
            coap_notifies=st["coap_notifies"])
    finally:
        server.stop()


SECTIONS = {
    "kernel": sec_kernel,
    "tenm": sec_tenm,
    "churn": sec_churn,
    "xdev": sec_xdev,
    "xcpp": sec_xcpp,
    "shared": sec_shared,
    "host": sec_host,
    "ws": sec_ws,
    "trunk": sec_trunk,
    "durable": sec_durable,
    "mixed": sec_mixed,
    "coap": sec_coap,
    "shards": sec_shards,
    "e2e": sec_e2e,
    "observe_overhead": sec_observe_overhead,
    "fault_overhead": sec_fault_overhead,
    "conn_scale": sec_conn_scale,
}

# (name, pin_cpu, deadline_s). Device sections run first —
# they are the artifact's reason to exist (VERDICT r2/r3/r4) — and in
# decreasing value order so a budget squeeze drops the cheapest claims.
PLAN = [
    ("kernel", False, 800),
    ("tenm", False, 800),
    ("churn", False, 500),
    ("xdev", False, 500),
    ("e2e", False, 600),
    ("xcpp", True, 400),
    ("host", True, 500),
    ("ws", True, 400),
    ("trunk", True, 400),
    ("durable", True, 400),
    ("mixed", True, 500),
    ("coap", True, 400),
    ("shards", True, 500),
    ("shared", True, 400),
    ("observe_overhead", True, 300),
    ("fault_overhead", True, 400),
    ("conn_scale", True, 800),
]
_SECTION_ORDER = ["kernel", "tenm", "churn", "xdev", "xcpp",
                  "shared", "host", "ws", "trunk", "durable", "mixed",
                  "coap", "shards", "e2e", "observe_overhead",
                  "fault_overhead", "conn_scale"]


def _probe_device(timeout_s: float) -> dict:
    """One killable child asks JAX for its devices; the supervisor
    itself never touches JAX, so each section child owns the chip
    alone. ``ok`` only for a TPU: bare jax.devices() falls back to the
    CPU where no accelerator is registered."""
    import subprocess as sp

    try:
        p = sp.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices(); "
             "assert d[0].platform == 'tpu', d; "
             "print(d[0].device_kind, len(d))"],
            env=dict(os.environ), timeout=timeout_s,
            capture_output=True, text=True)
    except sp.TimeoutExpired:
        return {"ok": False, "reason": f"probe hung >{timeout_s:.0f}s"}
    if p.returncode == 0:
        return {"ok": True, "device": (p.stdout or "").strip()}
    tail = (p.stderr or "").strip().splitlines()[-1:]
    return {"ok": False,
            "reason": f"rc={p.returncode} {tail[0][:160] if tail else ''}"}


def _compose(partial_dir: str, meta: dict) -> dict:
    """Merge every captured section file (canonical order) + supervisor
    metadata into the one cumulative artifact line."""
    merged: dict = {}
    for name in _SECTION_ORDER:
        path = os.path.join(partial_dir, f"section_{name}.json")
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except Exception:
                continue
            merged.update(data)

    platform = merged.get("kernel_platform", "none")
    value = merged.get("kernel_topics_per_sec", 0)
    final = {
        "metric": "route-matches/sec",
        "value": value,
        "unit": "topics/sec",
        # the MEASURED in-repo anchor (VERDICT r3 weak #8): the
        # host-oracle python trie walk on the same topic distribution
        "vs_host_oracle": merged.get("vs_host_oracle", 0),
        # the reference's published headline (1M msg/s sustained,
        # reference README.md:16) — the BASELINE.md-defined denominator
        "vs_baseline": round(value / 1_000_000, 3),
        "platform": platform,
    }
    final.update(merged)
    # both names stay: `platform` is the headline label, and the
    # artifact-schema lint (tests/test_bench_schema.py) pins the raw
    # `kernel_platform` capture so future runs can't silently drop it
    final["kernel_platform"] = platform
    # crossover point: smallest table size where the device kernel beats
    # the C++ per-message walk (the number that justifies the project)
    cross = None
    for n in CROSS_SIZES:
        dev = merged.get(f"dev_match_tps_{n}",
                         value if n == CROSS_SIZES[-1]
                         and platform not in ("cpu", "none") else None)
        cpp = merged.get(f"cpp_match_tps_{n}")
        if dev and cpp:
            final[f"dev_match_tps_{n}"] = dev
            if cross is None and dev > cpp:
                cross = n
    if cross is not None:
        final["crossover_filters"] = cross
    final.update(meta)
    return final


def _emit(final: dict) -> None:
    print(json.dumps(final), flush=True)


def supervise() -> None:
    import subprocess as sp
    import tempfile

    partial_dir = os.environ.get("BENCH_PARTIAL_DIR")
    if not partial_dir:
        partial_dir = tempfile.mkdtemp(prefix="emqx_bench_")
    budget = float(os.environ.get("BENCH_TOTAL_BUDGET_S", 3300))
    t_start = time.time()

    probe = _probe_device(
        timeout_s=float(os.environ.get("BENCH_PROBE_TIMEOUT_S", 120)))
    if not probe["ok"]:
        # a measurement path that finds no chip fails; it never runs a
        # CPU plan in the chip's place
        log(f"no TPU: {probe['reason']}")
        sys.exit(2)
    log(f"device: {probe['device']}")

    section_status: dict = {}
    meta = {"probe_ok": True, "probe_log": [probe["device"]],
            "sections": section_status}
    for name, pin_cpu, deadline in PLAN:
        remaining = budget - (time.time() - t_start)
        if remaining < 90:
            section_status[name] = "skipped: budget exhausted"
            log(f"section {name}: skipped, {remaining:.0f}s of budget left")
            continue
        timeout = min(deadline, remaining - 60)
        env = {**os.environ, "BENCH_SECTION": name,
               "BENCH_PARTIAL_DIR": partial_dir}
        if pin_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        log(f"=== section {name} (timeout {timeout:.0f}s, "
            f"{remaining:.0f}s budget left) ===")
        t0 = time.time()
        try:
            rc = sp.run([sys.executable, "-u", os.path.abspath(__file__)],
                        env=env, timeout=timeout).returncode
            section_status[name] = (f"ok ({time.time()-t0:.0f}s)" if rc == 0
                                    else f"failed rc={rc}")
        except sp.TimeoutExpired:
            section_status[name] = f"timeout after {timeout:.0f}s"
            log(f"section {name}: killed at {timeout:.0f}s deadline")
        # cumulative line lands on stdout after EVERY section — a later
        # failure or driver kill still leaves this tail
        _emit(_compose(partial_dir, meta))

    final = _compose(partial_dir, meta)
    _emit(final)
    sys.exit(0 if final.get("value") else 1)


def run_section(name: str) -> None:
    """Child entry: run one section inline, persisting partials as the
    section's own flush cadence dictates."""
    from emqx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    SECTIONS[name]()
    flush_results(name)


if __name__ == "__main__":
    if "--observe-overhead" in sys.argv:
        # standalone micro-run of the telemetry-cost proof (ISSUE 3):
        # same section the supervisor schedules, runnable in seconds
        run_section("observe_overhead")
        sys.exit(0)
    section = os.environ.get("BENCH_SECTION")
    if section:
        run_section(section)
    else:
        supervise()

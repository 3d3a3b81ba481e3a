"""One run of one cell: set-up, the measured window, the checks.

The system under test is the program's served path: ``BrokerApp`` with
the device router, the configuration's table resident in the device
trie, ``NativeBrokerServer(device_lane="on")`` on loopback TCP. Load
comes from ``loadgen`` processes on CPU cores of their own. What the
window delivers is compared with the plain reference (``reference``):
every delivery due, to exactly the subscribers whose filters match, with
every byte intact. The broker's counters show whether the device lane
served every publish of the window; a window it did not serve is void
and is measured once more (``run``). Before each measured window a warm
window sends the cell's own traffic unmeasured (``Session.warm_window``).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys
import tempfile
import time

import numpy as np

import loadgen
import readers
import reference
import registry

DRAIN_S = 60.0          # how long after the window a due delivery may come
WARM_S = 5.0            # the warm window before each measured one
TRACE_S = 5.0           # the traced part of a --trace 1 window (its end)
STALL_S = 3.5           # a planted pump stall, past the lane's 3 s deadline
METRICS = ("messages.device_failover", "messages.kernel.hostmatch")
LANE_STATS = ("lane_punts", "lane_stale", "lane_fallback")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


class Phases:
    """Set-up phases on the host clock, from process start."""

    def __init__(self, t_proc: float) -> None:
        self.t_proc = t_proc
        self.t = t_proc
        self.items: list[tuple[str, float]] = []

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.items.append((name, now - self.t))
        log(f"setup {name}: {now - self.t:.3f} s")
        self.t = now


def cpu_layout(traffic: dict) -> dict:
    """The generator processes share the last cores, one per process,
    and may move among them; the broker keeps the rest. Called before
    the broker touches JAX, so every thread it starts inherits its
    cores."""
    cpus = sorted(os.sched_getaffinity(0))
    n_gen = traffic["publisher_processes"] + traffic["receiver_processes"]
    if len(cpus) <= n_gen:
        gen = []
        broker = cpus
    else:
        gen = cpus[-n_gen:]
        broker = cpus[:-n_gen]
    os.sched_setaffinity(0, broker)
    return {"host_cores": os.cpu_count(), "broker": broker, "generators": gen}


def require_chip(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform={devs[0].platform}); "
                     "no result")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


# -- the cell's inputs, all from the seed -------------------------------------

def make_inputs(cell: dict, seed: int) -> dict:
    """Table, socket subscribers' filters and each publisher connection's
    topic pool. Every topic reaches at least one socket subscriber, and
    none reaches two filters of one subscriber (so the reference's
    delivery count per subscriber is 0 or 1)."""
    cfg, tr = cell["config"], cell["traffic"]
    table = registry.table(cfg["table"])
    ss = np.random.SeedSequence(seed % (1 << 64))
    r_table, r_subs, r_topics, r_pubs = (np.random.default_rng(s)
                                         for s in ss.spawn(4))
    n = cfg["filters"]
    filters = table.filters(n, r_table, cfg.get("table_params", {}))
    n_subs = cfg["subscriber_connections"]
    per_sub = cfg["filters_per_subscriber"]
    picks = []
    seen = set()
    for i in r_subs.permutation(len(filters)).tolist():
        f = filters[i]
        if f not in seen:
            seen.add(f)
            picks.append(f)
            if len(picks) == n_subs * per_sub:
                break
    sub_filters = [picks[k * per_sub:(k + 1) * per_sub]
                   for k in range(n_subs)]
    owner = [k for k in range(n_subs) for _ in range(per_sub)]
    fs = reference.FilterSet(picks)
    n_conns = cfg["publisher_connections"]
    want = n_conns * tr["topics_per_publisher"]
    if tr["topics_per_publisher"] > 4096:
        raise ValueError("a publisher may own at most 4096 topics")
    single = tr.get("single_delivery", False)
    pool, subs_of = [], []
    seen = set()
    for _ in range(50 * want):
        f = picks[int(r_topics.integers(0, len(picks)))]
        t = table.instantiate(f, r_topics, n)
        if t in seen:
            continue
        seen.add(t)
        hit = [owner[j] for j in fs.match(t)]
        if len(set(hit)) != len(hit) or (single and len(hit) != 1):
            continue
        pool.append(t)
        subs_of.append(sorted(hit))
        if len(pool) == want:
            break
    if len(pool) < n_conns:
        raise ValueError(f"only {len(pool)} usable topics for {n_conns} "
                         "publisher connections")
    order = r_topics.permutation(len(pool))
    conn_pools = [order[c::n_conns].tolist() for c in range(n_conns)]
    return {"filters": filters, "sub_filters": sub_filters,
            "topics": pool, "subs_of": subs_of, "conn_pools": conn_pools,
            "pub_seed": int(r_pubs.integers(0, 1 << 62)),
            "phase": float(r_pubs.random()),
            "exact_topic_share": float(np.mean(
                [any(not _wild(picks[j]) for j in fs.match(t))
                 for t in pool]))}


def _wild(filt: str) -> bool:
    return any(w in ("+", "#") for w in filt.split("/"))


# -- the instrumented seams ---------------------------------------------------

class LaneTap:
    """Wraps the model's submit and collect, the calls the lane pump
    makes into the router layer: records each batch's size and, in a
    traced run, the topics and a host span around each call. ``fault``
    breaks the window's answers on purpose (the control and the fault
    tests), or stands the pump still past the lane's stale deadline
    (``stall_once``: in the first window; ``stall``: in every one)."""

    def __init__(self, model, traced: bool, fault: str = None) -> None:
        self.sizes: list[int] = []
        self.batches: list[tuple[int, list]] = []
        self.recording = False
        self.keep_topics = False
        self.fault = fault
        self.n_answered = 0
        self.windows = 0
        self.stall_due = False
        self._prev = None
        self._submit = model.publish_batch_submit
        self._collect = model.publish_batch_collect
        span = None
        if traced:
            import jax

            span = jax.profiler.TraceAnnotation
        self._span = span
        model.publish_batch_submit = self.submit
        model.publish_batch_collect = self.collect

    def start_window(self) -> None:
        self.sizes.clear()
        self.batches.clear()
        self.stall_due = (self.fault == "stall" or (
            self.fault == "stall_once" and not self.windows))
        self.windows += 1
        self.recording = True

    def submit(self, topics, **kw):
        if self.recording:
            self.sizes.append(len(topics))
            if self.keep_topics:
                self.batches.append((time.monotonic_ns(), topics))
        if self._span is None:
            return self._submit(topics, **kw)
        with self._span("lane.submit"):
            return self._submit(topics, **kw)

    def collect(self, handle):
        if self._span is None:
            res = self._collect(handle)
        else:
            with self._span("lane.collect"):
                res = self._collect(handle)
        if self.stall_due and self.recording:
            self.stall_due = False
            time.sleep(STALL_S)
        elif self.fault and self.recording:
            res = self._break(res)
        return res

    def _break(self, res):
        matched, aux, slots, fallback = res
        matched = [list(m) for m in matched]
        n = len(matched)
        if self.fault == "control":
            # at-most-once: one lane message in a thousand is answered
            # with no subscriber, as a load-shedding shortcut would
            for i in range(n):
                if (self.n_answered + i) % 1000 == 999:
                    matched[i] = []
        elif self.fault == "half_batch":
            for i in range(n // 2, n):
                matched[i] = []
        elif self.fault == "alter_answer" and n >= 1:
            # the first answer of each batch is the previous batch's
            # answer to another topic: a wrong subscriber set
            prev, self._prev = self._prev, matched[-1]
            if prev is not None and prev != matched[0]:
                matched[0] = prev
        self.n_answered += n
        return matched, aux, slots, fallback


def hist_snapshot(metrics, name: str) -> dict:
    h = metrics.hist(name)
    if h is None:
        return {"count": 0, "sum_ns": 0}
    return {"count": int(h.count), "sum_ns": int(h.sum_ns)}


def snapshot(server, app, model) -> dict:
    st = server.fast_stats()
    return {"stats": {k: int(v) for k, v in st.items()},
            "metrics": {k: int(app.metrics.val(k)) for k in METRICS},
            "launches": int(model.launch_count),
            "hists": {k: hist_snapshot(app.metrics, k)
                      for k in readers.LANE_HISTS}}


def lane_counters(start: dict, end: dict, n_pub: int) -> dict:
    """What shows that the device lane did not serve every publish of a
    window: publishes it never took in, and each counter of a publish
    handed elsewhere (punted, stale-tripped, fallen back, failed over,
    matched on the host). All 0 in a window the lane served."""
    lane = {"lane_in_short": abs(
        n_pub - (end["stats"]["lane_in"] - start["stats"]["lane_in"]))}
    for k in LANE_STATS:
        lane[k] = end["stats"][k] - start["stats"][k]
    for k in METRICS:
        lane[k.split(".")[-1]] = end["metrics"][k] - start["metrics"][k]
    return lane


# -- one run ------------------------------------------------------------------

def run(cell: dict, seed: int, seconds: float, traced: bool, *,
        t_proc: float, layout: dict, chip: bool = True,
        fault: str = None) -> dict:
    """Set-up, the warm window and one measured window. A window the
    device lane did not serve in full (a stall of the whole host past
    the lane's 3 s deadline hands it to the broker's host path) is void:
    none of its numbers is reported. The lane reopens, the permits are
    earned again, the warm window runs again and one more window is
    measured; if that one is not served either, its lane counters fail
    the run's checks."""
    with Session(cell, seed, t_proc=t_proc, layout=layout, chip=chip,
                 traced=traced, fault=fault) as s:
        s.warm_window(WARM_S)
        out = s.window(seconds, traced)
        if not any(out["lane"].values()):
            return out
        log(f"window void, the device lane did not serve it: {out['lane']}")
        t = time.monotonic()
        s.reopen_lane()
        log(f"lane reopened and permits earned again in "
            f"{time.monotonic() - t:.3f} s")
        s.warm_window(WARM_S)
        again = s.window(seconds, traced)
        again["voided"] = [out["lane"]]
        return again


class Session:
    """Set-up once; then one window (a benchmark run) or several (the
    knee sweep). Entering builds the broker and the clients and earns
    every permit; leaving stops every process it started."""

    def __init__(self, cell: dict, seed: int, *, t_proc: float,
                 layout: dict, chip: bool = True, traced: bool = False,
                 fault: str = None) -> None:
        self.cell, self.seed, self.layout = cell, seed, layout
        self.chip, self.traced, self.fault = chip, traced, fault
        self.ph = Phases(t_proc)
        self.procs: list = []
        self.server = None
        self.t_first = None     # the session's first scheduled send, ns
        self.warm: list[dict] = []

    def __enter__(self) -> "Session":
        try:
            self._setup()
        except BaseException:
            self.close()
            raise
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        for c in getattr(self, "pubs", ()):
            try:
                c.send(("stop",))
            except OSError:
                pass
        for c in getattr(self, "recv", ()):
            try:
                c.send("stop")
            except OSError:
                pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        self.procs = []
        if self.server is not None:
            self.server.stop()
            self.server = None

    def _setup(self) -> None:
        import jax

        ph, cell = self.ph, self.cell
        self.devices = (require_chip(cell["chips"]) if self.chip
                        else jax.devices()[:1])
        dev = self.devices[0]
        log(f"device: {dev.platform} {dev.device_kind} x{len(self.devices)};"
            f" host cores {self.layout['host_cores']}, broker on "
            f"{self.layout['broker']}, generators on "
            f"{self.layout['generators']}")
        ph.mark("jax")

        from emqx_tpu import native
        from emqx_tpu.app import BrokerApp
        from emqx_tpu.broker import native_server as ns
        from emqx_tpu.config.config import Config

        if not native.available():
            raise RuntimeError(f"native build: {native.build_error()}")
        ph.mark("native library")

        cfg = cell["config"]
        conf = Config()
        for k, v in cfg["broker"].items():
            conf.put(k, v)
        app = self.app = BrokerApp.from_config(conf)
        model = self.model = app.broker.model
        inputs = self.inputs = make_inputs(cell, self.seed)
        ph.mark("inputs generated")
        for i, f in enumerate(inputs["filters"]):
            model.subscribe(f, i % model.n_sub_slots)
        ph.mark("table subscribed")
        model.refresh()
        jax.block_until_ready(model._trie_dev)
        ph.mark("trie built and uploaded")
        # compiled before the server exists: the server re-earns every
        # permit 60 s after it is built, and a cold compile inside that
        # span would put the re-earn in the window
        compiled = model.warm(ns.LANE_MAX_BATCH)
        ph.mark(f"programs ready ({len(compiled)})")
        model.telemetry = None          # the kernel fold: nothing reads it
        self.server = ns.NativeBrokerServer(port=0, app=app,
                                            device_lane="on")
        self.server.start()
        if not self.server.lane_open.is_set():
            raise RuntimeError("device lane did not open")
        ph.mark("server started")
        self.tap = LaneTap(model, self.traced, self.fault)
        self._clients()
        self.warm_permits()
        ph.mark(f"permits warmed ({self.granted} of {self.pairs} granted)")

    def _clients(self) -> None:
        cfg, tr, inputs = (self.cell["config"], self.cell["traffic"],
                           self.inputs)
        P, R = tr["publisher_processes"], tr["receiver_processes"]
        self.R = R
        ctx = mp.get_context("spawn")
        self.counters = ctx.RawArray("q", R * 2)
        topics_b = [t.encode() for t in inputs["topics"]]
        gen_cpus = self.layout["generators"]
        n_subs = cfg["subscriber_connections"]
        port = self.server.port
        self.recv = []
        for r in range(R):
            subs = [(k, f"bench-sub-{k}", inputs["sub_filters"][k])
                    for k in range(r, n_subs, R)]
            self.recv.append(self._spawn(ctx, loadgen.receiver_main, {
                "port": port, "cpus": gen_cpus, "subs": subs,
                "topics": topics_b, "payload_bytes": cfg["payload_bytes"],
                "slot": r}, self.counters))
        _wait(self.recv, "receivers connect")
        self.pubs = []
        n_conns = cfg["publisher_connections"]
        for p in range(P):
            conns = [(f"bench-pub-{c}", inputs["conn_pools"][c])
                     for c in range(p, n_conns, P)]
            self.pubs.append(self._spawn(ctx, loadgen.publisher_main, {
                "port": port, "cpus": gen_cpus, "proc": p,
                "conns": conns, "topics": topics_b,
                "conn_index": list(range(p, n_conns, P)),
                "n_conns": n_conns, "phase": inputs["phase"],
                "payload_bytes": cfg["payload_bytes"],
                "seed": inputs["pub_seed"] + p}))
        _wait(self.pubs, "publishers connect")
        self.ph.mark(f"clients connected ({n_subs} subscribers, {n_conns} "
                     f"publishers)")

    def _spawn(self, ctx, target, *args):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=target, args=(child, *args), daemon=True)
        p.start()
        self.procs.append(p)
        return parent

    def _count(self, k: int) -> int:
        return sum(self.counters[r * 2 + k] for r in range(self.R))

    def warm_permits(self) -> None:
        """Every (publisher, topic) pair publishes once on the Python
        path, which earns its C++ permit; the window then rides the
        fast path and the device lane only."""
        subs_of = self.inputs["subs_of"]
        pools = self.inputs["conn_pools"]
        self.pairs = sum(len(pool) for pool in pools)
        due = self._count(1) + sum(len(subs_of[t]) for pool in pools
                                   for t in pool)
        for c in self.pubs:
            c.send(("warm",))
        _wait(self.pubs, "permit warm-up")
        if self._drain(1, due, 120):
            raise TimeoutError("permit warm-up deliveries missing")
        deadline = time.monotonic() + 20
        while (_granted(self.server) < self.pairs
               and time.monotonic() < deadline):
            time.sleep(0.02)
        self.granted = _granted(self.server)
        time.sleep(0.3)         # the warm-up's telemetry deltas fold in

    def reopen_lane(self) -> None:
        """After a void window: wait until the broker has seen the lane's
        stale trip, sat out its back-off and opened the lane again, then
        restart the permits' 60 s clock and earn every permit anew (the
        trip's drain revoked some)."""
        from emqx_tpu.broker import native_server as ns

        server = self.server
        deadline = (time.monotonic() + ns.LANE_STALE_BACKOFF_S
                    + 2 * ns.HOUSEKEEP_INTERVAL + 60)
        while not (server.fast_stats()["lane_stale"]
                   == server._lane_stale_seen
                   and server._lane_on and server.lane_open.is_set()):
            if time.monotonic() > deadline:
                raise TimeoutError("the device lane did not reopen")
            time.sleep(0.05)
        server.flush_permits()
        server._last_permit_flush = time.monotonic()
        self.warm_permits()

    def _drain(self, slot: int, due: int, timeout: float) -> int:
        """Wait until counter ``slot`` (0: window deliveries, 1: marked
        ones) reaches ``due``, at most ``timeout`` s; returns how many
        are still missing."""
        deadline = time.monotonic() + timeout
        while self._count(slot) < due and time.monotonic() < deadline:
            time.sleep(0.01)
        return max(0, due - self._count(slot))

    def _due(self, sent: list) -> tuple:
        """The topic indices a window's publishers report and the
        deliveries they make due."""
        tidx = np.concatenate([np.frombuffer(s["tidx"], np.int64)
                               for s in sent])
        subs_of = self.inputs["subs_of"]
        return tidx, int(sum(len(subs_of[t]) for t in tidx.tolist()))

    def _go(self, seconds: float, rate: float, warm: bool) -> int:
        """Start the publishers' open loop 200 ms from now; returns its
        first scheduled send (ns)."""
        t0 = time.monotonic_ns() + 200_000_000
        if self.t_first is None:
            self.t_first = t0
        for c in self.pubs:
            c.send(("go", t0, seconds, rate, warm))
        return t0

    def warm_window(self, seconds: float, **params) -> None:
        """The cell's own traffic for ``seconds``, unmeasured, right
        before a measured window: the first window of a process held a
        send hiccup and the lane's first batches (PERF.md). Its messages
        carry the warm-up mark, so the receivers count them apart and
        record and check nothing of them; it drains before the measured
        window's snapshots, GC clock and lane tap start. The permits'
        re-earn clock (``PERMIT_TTL_S``) restarts once it has drained,
        so the measured window ends before the next re-earn. A warm
        window the lane did not serve (a host stall past its deadline)
        is run once more after the lane has reopened."""
        tr = dict(self.cell["traffic"], **params)
        for attempt in range(2):
            st0 = self.server.fast_stats()
            warm0 = self._count(1)
            t0 = self._go(seconds, tr["rate_per_s"], warm=True)
            sent = _wait(self.pubs, "warm window", timeout=seconds + 120)
            tidx, due = self._due(sent)
            missing = self._drain(1, warm0 + due, DRAIN_S)
            if missing:
                raise TimeoutError(
                    f"warm window: {missing} deliveries missing")
            st1 = self.server.fast_stats()
            tripped = {k: int(st1[k]) - int(st0[k]) for k in LANE_STATS}
            self.warm.append({"t0": t0, "seconds": seconds,
                              "sent": len(tidx), "lane": tripped})
            if attempt or not any(tripped.values()):
                break
            log(f"warm window not served by the lane: {tripped}")
            self.reopen_lane()
        time.sleep(0.3)         # its telemetry deltas fold in
        self.server._last_permit_flush = time.monotonic()

    def window(self, seconds: float, traced: bool,
               trace_s: float = TRACE_S, **params) -> dict:
        """One measured window; ``params`` override the traffic's
        ``rate_per_s`` (the sweep's rates)."""
        import jax

        tr = dict(self.cell["traffic"], **params)
        server, app, model, tap = self.server, self.app, self.model, self.tap
        gcc = loadgen.GcClock()
        start = snapshot(server, app, model)
        recv0 = self._count(0)
        tap.start_window()
        t0 = self._go(seconds, tr["rate_per_s"], warm=False)
        setup_s = self.t_first / 1e9 - self.ph.t_proc
        for c in self.recv:
            c.send("reset_gc")
        gcc.reset()
        t_end = t0 + int(seconds * 1e9)
        trace_dir = win = None
        if traced:
            # the window's last seconds only: collecting the trace takes
            # about 13 s per traced second at 2,000 msgs/s (TPU v5e), and a
            # run with a void window collects two. The profiler is stopped
            # after the window has drained, when no frame waits on the
            # lane's 3 s stale deadline
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans only, no per-call
            opts.host_tracer_level = 1      # Python tracing in the pump
            _sleep_until(t_end - int(min(trace_s, seconds) * 1e9))
            t = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            log(f"trace: profiler started in {time.monotonic() - t:.3f} s")
            tap.keep_topics = True
            lo = time.monotonic_ns()
            with jax.profiler.TraceAnnotation("bench.window"):
                _sleep_until(t_end)
            win = (lo, time.monotonic_ns())
            tap.keep_topics = False
        else:
            _sleep_until(t_end)
        sent = _wait(self.pubs, "publishers report", timeout=seconds + 120)
        cat = lambda k: np.concatenate(  # noqa: E731
            [np.frombuffer(s[k], np.int64) for s in sent])
        msg, tidx, sched, sent_t = (cat("msg"), cat("tidx"), cat("sched"),
                                    cat("sent"))
        self._drain(0, recv0 + self._due(sent)[1], DRAIN_S)
        time.sleep(0.5)         # a stray or duplicate delivery shows by now
        drained_ns = time.monotonic_ns()
        gc_window = gcc.as_dict()
        tap.recording = False
        end = snapshot(server, app, model)
        lane = lane_counters(start, end, len(msg))
        trace_red = None
        if traced:
            t = time.monotonic()
            jax.profiler.stop_trace()
            log(f"trace: profiler stopped in {time.monotonic() - t:.3f} s")
        stats_dev = self.devices[0].memory_stats() or {}
        for c in self.recv:
            c.send("report")
        got = _wait(self.recv, "receivers report")
        if traced:
            if not any(lane.values()):      # a void window is not read
                import trace as trace_mod

                trace_red = trace_mod.reduce(trace_mod.load(trace_dir),
                                             _step_module(model))
            _rmtree(trace_dir)
        return {
            "setup_s": setup_s, "phases": self.ph.items, "t0": t0,
            "t_end": t_end, "drained_ns": drained_ns, "traffic": tr,
            "msg": msg, "tidx": tidx, "sched": sched, "sent": sent_t,
            "recv": got, "pub_gc": [s["gc"] for s in sent], "gc": gc_window,
            "start": start, "end": end, "lane": lane, "voided": [],
            "sizes": list(tap.sizes),
            "batches": list(tap.batches), "window_host": win,
            "trace": trace_red,
            "memory_peak_bytes": int(stats_dev.get("peak_bytes_in_use", 0)),
            "granted": self.granted, "pairs": self.pairs,
            "warm": list(self.warm),
            "permit_age_s": (time.monotonic()
                             - server._last_permit_flush),
            "inputs": self.inputs,
        }


def _wait(conns, what: str, timeout: float = 120.0) -> list:
    out = []
    for c in conns:
        if not c.poll(timeout):
            raise TimeoutError(f"{what}: no answer in {timeout} s")
        out.append(c.recv())
    return out


def _granted(server) -> int:
    while True:
        try:
            return sum(len(s) for s in list(server._granted.values()))
        except RuntimeError:        # resized by the poll thread meanwhile
            continue


def _sleep_until(t_ns: int) -> None:
    while True:
        left = t_ns - time.monotonic_ns()
        if left <= 0:
            return
        time.sleep(min(left / 1e9, 0.5))


def _rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def _step_module(model) -> str:
    """The router step program's name as XLA reports it in the trace."""
    import re

    tables = (model._trie_dev, model._rowmap_dev, model._pool_dev)
    args, _ = model._batch_args([""] * 64)
    text = model._step.lower(*tables, *args).as_text()
    m = re.search(r"module @(\S+)", text)
    return m.group(1) if m else "jit_router_step"

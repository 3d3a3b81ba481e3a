"""CPU self-tests of the benchmark's own code: the trace reduction, the
roofline byte count, the plain reference matcher, the windowed histogram
delta and the lane segment readers, finding parts by name, and runs of
the harness through the served path at a small size: its result line,
its warm window and its failures.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import random

import pytest

import evaluate
import harness
import loadgen
import readers
import reference
import registry
import stats
import trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


# -- trace reduction -----------------------------------------------------------

def _synthetic_trace() -> dict:
    ms = 1_000_000
    return {
        "/host:CPU": {
            "main": [("bench.window", 100 * ms, 100 * ms)],
            "pump": [("lane.submit", 120 * ms, 5 * ms),
                     ("lane.collect", 150 * ms, 20 * ms)],
        },
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(7)", 90 * ms, 20 * ms),
                            ("jit_step(7)", 130 * ms, 10 * ms),
                            ("jit_other", 180 * ms, 5 * ms)],
            "XLA Ops": [("while.1", 90 * ms, 20 * ms),
                        ("fusion.2", 130 * ms, 6 * ms),
                        ("fusion.3", 134 * ms, 6 * ms),
                        ("fusion.2", 180 * ms, 5 * ms)],
        },
    }


def test_trace_reduce_synthetic():
    r = trace.reduce(_synthetic_trace(), "jit_step")
    assert r["window_s"] == pytest.approx(0.1)
    # ops clipped to [100, 200] ms: 10 + (130..140) + 5 = 25 ms busy
    assert r["busy_s"] == pytest.approx(0.025)
    # only the launch wholly inside the window counts
    assert r["step_launches"] == 1
    assert r["step_device_s"] == pytest.approx(0.010)
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx(0.010)
    assert ops["fusion.2"] == pytest.approx(0.011)
    gaps = dict(r["idle_gaps"])
    # gaps: 110-130 (mid 120: submit), 140-180 (mid 160: collect),
    # 185-200 (mid 192.5: none)
    assert gaps["lane.submit"] == pytest.approx(0.020)
    assert gaps["lane.collect"] == pytest.approx(0.040)
    assert gaps["no_span"] == pytest.approx(0.015)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_trace_gap_under_the_pumps_gate():
    """A gap inside the pump's ``lane.gate`` is charged to it, one inside
    a ``lane.collect`` nested in that gate to the collect, one inside
    ``lane.idle`` or ``lane.respond`` to those."""
    ms = 1_000_000
    tr = {
        "/host:CPU": {
            "main": [("bench.window", 0, 100 * ms)],
            "pump": [("lane.idle", 0, 10 * ms),
                     ("lane.gate", 20 * ms, 45 * ms),
                     ("lane.collect", 22 * ms, 6 * ms),
                     ("lane.respond", 70 * ms, 20 * ms)],
        },
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 10 * ms, 10 * ms),
                        ("fusion.1", 30 * ms, 30 * ms),
                        ("fusion.1", 62 * ms, 8 * ms),
                        ("fusion.1", 90 * ms, 10 * ms)],
        },
    }
    # gaps: 0-10 (mid 5: idle), 20-30 (mid 25: the collect), 60-62 (mid
    # 61: the gate, after its collect), 70-90 (mid 80: respond)
    r = trace.reduce(tr)
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"lane.idle": 0.010, "lane.collect": 0.010,
                                  "lane.gate": 0.002, "lane.respond": 0.020})


def test_trace_reduce_recorded():
    """A slice of a real TPU v5e trace of a --trace 1 run, recorded on
    the chip: every number the reduction gives is pinned."""
    with open(os.path.join(HERE, "data", "trace_v5e.json")) as f:
        rec = json.load(f)
    tr = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
          for p, lines in rec["trace"].items()}
    r = trace.reduce(tr, rec["module"])
    want = rec["expect"]
    assert r["step_launches"] == want["step_launches"]
    assert r["step_device_s"] == pytest.approx(want["step_device_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert 0 < r["busy_s"] <= r["window_s"]


def test_trace_without_window_or_device_is_an_error():
    tr = _synthetic_trace()
    del tr["/host:CPU"]["main"]
    with pytest.raises(ValueError):
        trace.reduce(tr, "jit_step")
    tr = _synthetic_trace()
    del tr["/device:TPU:0"]
    with pytest.raises(ValueError):
        trace.reduce(tr, "jit_step")


# -- roofline bytes --------------------------------------------------------------

def test_roofline_bytes_hand_worked():
    w = reference.PlainWalk(["a/b", "a/+", "a/#", "+/b", "x/y"])
    nbytes, nmatch = w.walk(["a/b"])
    # level 0, root: '#' entry 4 + exact probe 12 + '+' entry 4 = 20
    # level 1, {a, +}: 2 x 20 = 40; a's '#' child matches a/#
    # level 2, {a/b, a/+, +/b}, ended: 3 x ('#' 4 + own filter 4) = 24
    # 4 matches x 4 row bytes = 16
    assert nmatch.tolist() == [4]
    assert nbytes.tolist() == [20 + 40 + 24 + 16]


def test_plain_walk_agrees_with_reference():
    rng = random.Random(5)
    words = ["a", "b", "c", ""]
    filters = set()
    for _ in range(300):
        n = rng.randint(1, 4)
        ws = [rng.choice(words + ["+"]) for _ in range(n)]
        if rng.random() < 0.3:
            ws[-1] = "#"
        if rng.random() < 0.1:
            ws[0] = "$s"
        filters.add("/".join(ws))
    filters = sorted(filters)
    w = reference.PlainWalk(filters)
    topics = ["/".join(rng.choice(words + ["$s"]) for _ in
                       range(rng.randint(1, 5))) for _ in range(300)]
    _, nmatch = w.walk(topics)
    for t, n in zip(topics, nmatch.tolist()):
        assert n == sum(reference.topic_matches(f, t) for f in filters), t


# -- the plain reference matcher ---------------------------------------------------

@pytest.mark.parametrize("filt,topic,want", [
    ("a/#", "a", True),            # '#' matches its parent level
    ("a/#", "a/b/c", True),
    ("#", "a/b", True),
    ("#", "$SYS/x", False),         # no root wildcard on a $-topic
    ("+/x", "$SYS/x", False),
    ("$SYS/#", "$SYS/x", True),
    ("$SYS/+", "$SYS/x", True),
    ("a/+/b", "a//b", True),        # '+' matches an empty level
    ("+/+", "/", True),
    ("a/b", "a/b/", False),
    ("a/+", "a/b/c", False),
    ("a/b/#", "a/b", True),
    ("+", "a", True),
    ("+", "", True),
    ("/a", "a", False),
])
def test_topic_matches_edge_cases(filt, topic, want):
    assert reference.topic_matches(filt, topic) is want
    assert (len(reference.FilterSet([filt]).match(topic)) == 1) is want


def test_filter_set_agrees_with_definition():
    rng = random.Random(11)
    words = ["a", "b", "", "$x"]
    filters = ["/".join(rng.choice(words + ["+", "#"]) for _ in
                        range(rng.randint(1, 4))) for _ in range(200)]
    filters = [f for f in filters if "#" not in f[:-1]]
    fs = reference.FilterSet(filters)
    for _ in range(300):
        t = "/".join(rng.choice(words) for _ in range(rng.randint(1, 4)))
        got = sorted(fs.match(t))
        want = [i for i, f in enumerate(filters)
                if reference.topic_matches(f, t)]
        assert got == want, t


# -- windowed histogram delta, the lane segment readers ---------------------

def test_hist_delta_counts_only_the_window():
    start = {"count": 1000, "sum_ns": 1000 * 1_000_000}     # the set-up's
    end = {"count": 1100, "sum_ns": start["sum_ns"] + 100 * 40_000 + 7}
    d = stats.hist_delta(start, end)
    assert d == {"count": 100, "sum_ns": 100 * 40_000 + 7}
    assert stats.hist_delta(start, start) == {"count": 0, "sum_ns": 0}


@pytest.mark.parametrize("name", sorted(readers.LANE_SEGMENTS))
def test_lane_segment_reader(name):
    """Each segment's reader is the exact mean of its own histogram's
    window delta, in ms, and None where the window observed nothing."""
    read = registry.metric_reader(name.replace("_mean_ms",
                                               "_mean_ms.open"))
    empty = {h: {"count": 0, "sum_ns": 0} for h in readers.LANE_HISTS}
    assert read({"lane_hists": empty}) is None
    hists = {h: {"count": 3 + i, "sum_ns": 1_000_003 * (i + 1)}
             for i, h in enumerate(readers.LANE_HISTS)}
    i = readers.LANE_HISTS.index(readers.LANE_SEGMENTS[name])
    assert read({"lane_hists": hists}) == 1_000_003 * (i + 1) / (3 + i) / 1e6


def test_segments_line_sums_the_six():
    ms = 1_000_000
    hists = {h: {"count": 4, "sum_ns": 4 * (i + 1) * ms}
             for i, h in enumerate(readers.LANE_SEGMENTS.values())}
    hists[readers.LANE_TOTAL] = {"count": 4, "sum_ns": 4 * 21 * ms}
    line = evaluate.segments_line(hists)
    assert "host_park 1.0 (4)" in line and "host_deliver 6.0 (4)" in line
    assert "sum 21.0;" in line and line.endswith("lane_total 21.0 (4)")
    hists[readers.LANE_SEGMENTS["lane_wait_mean_ms"]] = {"count": 0,
                                                         "sum_ns": 0}
    assert "lane_wait None (0)" in evaluate.segments_line(hists)
    assert "sum None;" in evaluate.segments_line(hists)


# -- finding parts by name ---------------------------------------------------------

def test_every_cell_and_metric_resolves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = registry.load_cell(ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert registry.table(cell["config"]["table"]).filters
        assert cell["per_layer"] and cell["end_to_end"]
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


@pytest.mark.parametrize("kind,name", [
    ("workload", "no_such.cell"), ("traffic", "no_such_mix"),
    ("table", "no_such_table"), ("metric", "no_such_metric")])
def test_unknown_names_are_errors(kind, name):
    with pytest.raises(registry.UnknownName):
        if kind == "workload":
            registry.load_cell(ROOT, name)
        elif kind == "traffic":
            registry.load_traffic(name)
        elif kind == "table":
            registry.table(name)
        else:
            registry.metric_reader(name)


def test_unknown_device_kind_is_an_error():
    assert evaluate._peak("TPU v5 lite")()["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        evaluate._peak("cpu")()


# -- one run through the served path ---------------------------------------------

def _small_cell(workload: str) -> dict:
    cell = registry.load_cell(ROOT, workload)
    cell["config"] = dict(cell["config"], filters=20_000,
                          subscriber_connections=12,
                          filters_per_subscriber=4,
                          publisher_connections=4)
    cell["traffic"] = dict(cell["traffic"], topics_per_publisher=24,
                           publisher_processes=1, receiver_processes=1,
                           rate_per_s=300)
    return cell


def _run(workload: str, fault=None, seconds=2.0):
    import jax

    cell = _small_cell(workload)
    layout = {"host_cores": os.cpu_count(),
              "broker": sorted(os.sched_getaffinity(0)),
              "generators": []}
    out = harness.run(cell, 2**31 + 77, seconds, False, t_proc=0.0,
                      layout=layout, chip=False, fault=fault)
    res, lines, checks = evaluate.evaluate(cell, out, seconds, False,
                                           jax.devices()[0])
    return cell, res, lines, out


def test_result_line_schema_and_correct_run():
    cell, res, lines, _ = _run("p2p_1m.knee80")
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert {"lane_in_short", "lane_stale", "lane_punts", "lane_fallback",
            "device_failover", "hostmatch"} <= set(line["checks"])
    assert any(ln.startswith("device lane served the window: True")
               for ln in lines), lines
    assert any(ln.startswith("stalls in the window:") for ln in lines)
    assert any(ln.startswith("lane segments, mean ms per message")
               for ln in lines)


def test_warm_window_is_outside_the_measurement():
    """The warm window sends the cell's traffic before the measured one;
    none of its publishes is in the measured population, the lane
    counters or the lane histograms, and set-up ends at its first
    scheduled send (``_run`` starts the process clock at 0)."""
    _, res, lines, out = _run("p2p_1m.knee80")
    (w,) = out["warm"]
    assert w["sent"] >= 0.9 * harness.WARM_S * 300
    assert not any(w["lane"].values()), w
    assert w["t0"] + w["seconds"] * 1e9 <= out["t0"]
    assert res["metrics"]["setup_s"]["value"] == w["t0"] / 1e9
    n_pub = len(out["msg"])
    assert res["attempted"] == n_pub > 0
    assert not (out["msg"] & loadgen.WARM_BIT).any()
    assert out["sched"].min() >= out["t0"]
    s, e = out["start"], out["end"]
    assert e["stats"]["lane_in"] - s["stats"]["lane_in"] == n_pub
    assert {k: v["count"] for k, v in evaluate.lane_hists(out).items()} \
        == dict.fromkeys(readers.LANE_HISTS, n_pub)
    assert res["correct"] is True, res["checks"]


class _WarmOnly(harness.Session):
    """A session with nothing behind it: the warm window's own steps are
    stand-ins, the lane's stale counter moves in the first warm window
    only."""

    def __init__(self):
        self.cell = {"traffic": {"rate_per_s": 300}}
        self.warm, self.pubs, self.reopened, self.calls = [], [], 0, 0
        session = self

        class Server:
            _last_permit_flush = 0.0

            def fast_stats(self):
                session.calls += 1
                stale = 1 if session.calls >= 2 else 0
                return dict.fromkeys(harness.LANE_STATS, 0) | {
                    "lane_stale": stale}

        self.server = Server()

    def _count(self, slot):
        return 0

    def _go(self, seconds, rate, warm):
        assert warm
        return 1_000 * (len(self.warm) + 1)

    def _due(self, sent):
        return [0] * 7, 0

    def reopen_lane(self):
        self.reopened += 1


def test_warm_window_the_lane_did_not_serve_runs_again(monkeypatch):
    """A host stall inside the warm window trips the lane: the lane
    reopens and the warm window runs once more, so the measured window
    does not spend the run's one void window on it; the permits' re-earn
    clock restarts after the last warm window has drained."""
    monkeypatch.setattr(harness, "_wait", lambda conns, what, timeout: [])
    monkeypatch.setattr(harness.time, "sleep", lambda s: None)
    s = _WarmOnly()
    s.warm_window(5.0)
    assert s.reopened == 1
    first, second = s.warm
    assert first["lane"]["lane_stale"] == 1
    assert not any(second["lane"].values())
    assert s.server._last_permit_flush > 0


@pytest.mark.parametrize("fault", ["stall_once", "stall"])
def test_window_the_lane_did_not_serve(fault, monkeypatch):
    """A pump that stands still past the lane's 3 s deadline hands the
    window to the broker's host path, with every answer still right. That
    window is void and one more is measured: once, the run is correct on
    the second window; in every window, the lane counters fail it."""
    from emqx_tpu.broker import native_server as ns

    monkeypatch.setattr(ns, "LANE_STALE_BACKOFF_S", 1.0)
    _, res, lines, out = _run("p2p_1m.knee80", fault=fault)
    assert any(ln.startswith("windows void before this one (lane "
                             "counters): 1") for ln in lines), lines
    # the warm window runs again before the window that replaces the
    # void one; set-up still ends at the first warm window's first send
    first, second = out["warm"]
    assert first["t0"] < second["t0"]
    assert second["t0"] + second["seconds"] * 1e9 <= out["t0"]
    assert res["metrics"]["setup_s"]["value"] == first["t0"] / 1e9
    assert res["checks"]["missing"]["value"] == 0
    if fault == "stall_once":
        assert res["correct"] is True, res["checks"]
        assert set(res["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    else:
        assert res["correct"] is False
        assert res["checks"]["lane_stale"]["value"] > 0


@pytest.mark.parametrize("fault,seconds", [
    ("control", 4.5), ("half_batch", 2.0), ("alter_answer", 2.0)])
def test_broken_answers_fail_the_comparison(fault, seconds):
    # the control drops one lane message in a thousand: the window has
    # to carry more than a thousand publishes
    _, res, _, _ = _run("p2p_1m.knee80", fault=fault, seconds=seconds)
    assert res["attempted"] > 1000 or fault != "control"
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] > 0
    if fault == "alter_answer":
        assert res["checks"]["extra"]["value"] > 0

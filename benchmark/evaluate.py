"""From what one run recorded to its result line: the comparison with
the reference that decides ``correct``, the end-to-end metrics, the
per-layer metrics of a traced run, and the earlier lines that say what
the window looked like."""

from __future__ import annotations

import json
import os

import numpy as np

import readers
import reference
import registry
import stats

HERE = os.path.dirname(os.path.abspath(__file__))
KEY = 1 << 12           # (message, subscriber) pair key: msg * KEY + sub


def deliveries(out: dict) -> dict:
    """Expected against received, pair by pair."""
    subs_of = out["inputs"]["subs_of"]
    msg, tidx, sched = out["msg"], out["tidx"], out["sched"]
    n_exp = np.fromiter((len(subs_of[t]) for t in tidx.tolist()), np.int64,
                        len(tidx))
    pos = np.repeat(np.arange(len(msg)), n_exp)
    exp_sub = np.fromiter((s for t in tidx.tolist() for s in subs_of[t]),
                          np.int64, int(n_exp.sum()))
    exp_key = msg[pos] * KEY + exp_sub
    rsub = np.concatenate([np.frombuffer(r["sub"], np.int32)
                           for r in out["recv"]]).astype(np.int64)
    rmsg = np.concatenate([np.frombuffer(r["msg"], np.int64)
                           for r in out["recv"]])
    rt = np.concatenate([np.frombuffer(r["t"], np.int64) for r in out["recv"]])
    rok = np.concatenate([np.frombuffer(r["ok"], np.int8)
                          for r in out["recv"]])
    rkey = np.where(rmsg >= 0, rmsg * KEY + rsub, -1)
    uniq, first, count = np.unique(rkey, return_index=True,
                                   return_counts=True)
    valid = uniq >= 0
    if len(uniq):
        at = np.minimum(np.searchsorted(uniq, exp_key), len(uniq) - 1)
        found = uniq[at] == exp_key
        arrive = np.where(found, rt[first[at]], -1)
    else:
        found = np.zeros(len(exp_key), bool)
        arrive = np.full(len(exp_key), -1, np.int64)
    lat_ns = np.where(found, arrive - sched[pos], np.iinfo(np.int64).max)
    extra_keys = uniq[valid & ~np.isin(uniq, exp_key)]
    bad_msgs = set((msg[pos[~found]]).tolist())
    bad_msgs |= set((extra_keys // KEY).tolist())
    bad_msgs |= set((rmsg[(rok == 0) & (rmsg >= 0)]).tolist())
    return {
        "due": len(exp_key),
        "missing": int((~found).sum()),
        "extra": int(len(extra_keys)),
        "duplicate": int((count[valid] - 1).sum()),
        "corrupt": int((rok == 0).sum()),
        "failed_msgs": len(bad_msgs & set(msg.tolist())) + int(
            (rmsg < 0).sum()),
        "lat_ns": lat_ns, "found": found, "pos": pos, "arrive": arrive,
    }


def stall_line(out: dict, late) -> str:
    """Where a stall in the window lay: the stretches in which a
    receiver's loop stood still (the host stopped), and the span of
    sends that left more than 200 ms late (the generator stopped, or the
    broker stopped reading its sockets), in seconds from the window's
    start."""
    t0 = out["t0"]
    stood = [(round((s - t0) / 1e9, 3), round(d / 1e6, 1))
             for r in out["recv"]
             for s, d in zip(r["stood_still"][::2], r["stood_still"][1::2])]
    far = out["sched"][late > 200_000_000]
    span = (f"{len(far)} sends from {(far.min() - t0) / 1e9:.3f} s to "
            f"{(far.max() - t0) / 1e9:.3f} s" if len(far) else "none")
    return (f"stalls in the window: receivers stood still (at s, for ms) "
            f"{stood}; sends more than 200 ms late: {span}")


def lane_hists(out: dict) -> dict:
    """The window's delta of each lane histogram, by histogram name."""
    s, e = out["start"]["hists"], out["end"]["hists"]
    return {k: stats.hist_delta(s[k], e[k]) for k in readers.LANE_HISTS}


def segments_line(hists: dict) -> str:
    """The six lane segments' means, their sum and the whole's mean, in
    ms per lane message, with the count of messages each observed."""
    means = {name: readers.hist_mean_ms(hists[h])
             for name, h in readers.LANE_SEGMENTS.items()}
    parts = ", ".join(f"{name[:-len('_mean_ms')]} {v} "
                      f"({hists[h]['count']})" for (name, h), v in
                      zip(readers.LANE_SEGMENTS.items(), means.values()))
    known = [v for v in means.values() if v is not None]
    total = hists[readers.LANE_TOTAL]
    return (f"lane segments, mean ms per message (messages): {parts}; sum "
            f"{sum(known) if len(known) == len(means) else None}; "
            f"lane_total {readers.hist_mean_ms(total)} ({total['count']})")


def start_line(out: dict, late) -> str:
    """How late the sends of the window's first 200 ms left, beside the
    latest send of the whole window and when it was due."""
    t0 = out["t0"]
    first = out["sched"] < t0 + 200_000_000
    if not len(late):
        return "window start: no sends"
    k = int(np.argmax(late))
    head = float(late[first].max()) / 1e6 if first.any() else None
    return (f"window start: {int(first.sum())} sends in the first 200 ms, "
            f"lateness max {head} ms; the window's latest send "
            f"{float(late[k]) / 1e6} ms late, due at "
            f"{(int(out['sched'][k]) - t0) / 1e9:.3f} s")


def _peak(kind: str):
    def get():
        with open(os.path.join(HERE, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if kind not in table:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return table[kind]
    return get


def roofline_bytes(out: dict):
    """Mean plain-walk bytes per launch over the traced window's
    launches (their real topics; padding rows are not work)."""
    lo, hi = out["window_host"]
    batches = [b for t, b in out["batches"] if lo <= t <= hi]
    if not batches:
        return None
    walk = reference.PlainWalk(out["inputs"]["filters"])
    topics = sorted({t for b in batches for t in b})
    nbytes, _ = walk.walk(topics)
    per = dict(zip(topics, nbytes.tolist()))
    return float(np.mean([sum(per[t] for t in b) for b in batches]))


def evaluate(cell: dict, out: dict, seconds: float, traced: bool,
             device) -> tuple[dict, list[str], list[str]]:
    """(result line, earlier lines, check lines)."""
    tr = out["traffic"]
    d = deliveries(out)
    lines = []
    s, e = out["start"], out["end"]
    lane_in = e["stats"]["lane_in"] - s["stats"]["lane_in"]
    launches = e["launches"] - s["launches"]
    n_pub = len(out["msg"])
    # the answers, and that the device lane served every publish of the
    # window: its numbers are the chip's only then
    checks = {
        "missing": (d["missing"], 0),
        "extra": (d["extra"], 0),
        "duplicate": (d["duplicate"], 0),
        "corrupt": (d["corrupt"], 0),
        **{k: (v, 0) for k, v in out["lane"].items()},
    }
    correct = all(v <= lim for v, lim in checks.values())
    lines.append(f"device lane served the window: "
                 f"{not any(out['lane'].values())} {out['lane']}")
    lines.append(f"windows void before this one (lane counters): "
                 f"{len(out['voided'])} {out['voided']}")
    lines.append("warm windows before this one (s from its start, "
                 "length s, sends, lane counters moved): " + str(
                     [(round((w["t0"] - out["t0"]) / 1e9, 3), w["seconds"],
                       w["sent"], w["lane"]) for w in out["warm"]]))

    computed = {"setup_s": out["setup_s"]}
    # a delivery that never came counts the whole wait for it
    lat = np.where(d["found"], d["lat_ns"],
                   out["drained_ns"] - out["sched"][d["pos"]]
                   ).astype(np.float64)
    computed["p50_ms"] = stats.percentile(lat, 0.50) / 1e6
    computed["p95_ms"] = stats.percentile(lat, 0.95) / 1e6
    p99 = stats.percentile(lat, 0.99) / 1e6
    lines.append(f"latency: {d['due']} deliveries due from {n_pub} "
                 f"publishes; p50 {computed['p50_ms']} ms, p95 "
                 f"{computed['p95_ms']} ms, p99 {p99} ms, max "
                 f"{float(np.max(lat)) / 1e6} ms")
    late = out["sent"] - out["sched"]
    lines.append(
        f"generator: {n_pub} sends, lateness p50 "
        f"{stats.percentile(late, 0.5) / 1e6} ms p95 "
        f"{stats.percentile(late, 0.95) / 1e6} ms max "
        f"{float(late.max()) / 1e6 if len(late) else 0} ms; offered "
        f"{tr['rate_per_s']} msgs/s, sent {n_pub / seconds} msgs/s")
    sizes = np.asarray(out["sizes"], np.int64)
    if len(sizes):
        bucket = np.maximum(64, 2 ** np.ceil(np.log2(np.maximum(sizes, 1))))
        hist = {int(b): int(n) for b, n in
                zip(*np.unique(bucket, return_counts=True))}
        lines.append(f"lane batches: {len(sizes)} launches, mean "
                     f"{float(sizes.mean())}, p50 {float(np.median(sizes))}"
                     f", max {int(sizes.max())}; per bucket {hist}")
    lines.append(f"lane: in {lane_in} of {n_pub} publishes, launches "
                 f"{launches}, lane_out {e['stats']['lane_out'] - s['stats']['lane_out']}; "
                 f"permits {out['granted']} of {out['pairs']} granted, "
                 f"{out['permit_age_s']:.1f} s since the last permit flush")
    moved = {k: e["stats"][k] - s["stats"].get(k, 0) for k in e["stats"]
             if e["stats"][k] != s["stats"].get(k, 0)}
    lines.append(f"host counters moved in the window: {moved}")
    lines.append(stall_line(out, late))
    lines.append(start_line(out, late))
    hists = lane_hists(out)
    lines.append(segments_line(hists))
    gcs = out["gc"]
    lines.append(f"gc in the window: broker {gcs['n']} pauses, "
                 f"{gcs['total_ms']:.3f} ms total, {gcs['max_ms']:.3f} ms "
                 f"max; publishers {out['pub_gc']}; receivers "
                 f"{[r['gc'] for r in out['recv']]}")
    lines.append("setup phases: " + ", ".join(
        f"{n} {t:.3f}" for n, t in out["phases"]))
    lines.append(f"inputs: {len(out['inputs']['filters'])} filters, "
                 f"{len(out['inputs']['topics'])} topics, "
                 f"{out['inputs']['exact_topic_share']:.3f} of them reach "
                 f"an exact subscription")

    metrics = {}
    result = {"correct": correct, "attempted": n_pub,
              "failed": d["failed_msgs"], "metrics": metrics}
    if traced:
        ctx = {"gen_late_ns": late, "lane_hists": hists,
               "trace": out["trace"], "peak": _peak(device.device_kind),
               "bytes_per_launch": roofline_bytes(out)}
        for m in cell["per_layer"]:
            v = registry.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] in computed:
                metrics[m["name"]] = {"value": float(computed[m["name"]]),
                                      "unit": m["unit"]}
    result["device"] = {"platform": device.platform,
                        "kind": device.device_kind,
                        "count": cell["chips"],
                        "memory_peak_bytes": out["memory_peak_bytes"]}
    if traced and out["trace"]:
        t = out["trace"]
        result["device"]["busy_s"] = t["busy_s"]
        result["device"]["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              t["device_ops"]],
                               "idle_gaps": [list(x) for x in
                                             t["idle_gaps"]]}
        lines.append(f"trace: {t['step_launches']} step launches, "
                     f"{t['step_device_s']} s of step device time, busy "
                     f"{t['busy_s']} s of {t['window_s']} s")
    result["checks"] = {k: {"value": int(v), "limit": lim}
                        for k, (v, lim) in checks.items()}
    check_lines = [f"check {k}: {int(v)} (limit {lim})"
                   for k, (v, lim) in checks.items()]
    return result, lines, check_lines

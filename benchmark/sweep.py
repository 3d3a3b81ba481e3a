"""Knee sweep for an open-loop cell: one process, one set-up, one
window per offered rate, then a short traced window at the same rate for
the device's idle share. Prints one JSON line per rate and a table. The
benchmark's own runs never call it; its tables are what the ``knee80``
traffic files and ``PERF.md`` record.

    python3 benchmark/sweep.py --workload p2p_1m.knee80 \\
        --seed 7 --loads 250,1000,2000,2500,3500 --seconds 15

The broker re-earns every publish permit every 60 s
(``PERMIT_TTL_S``); a sweep outlives that, so before each window the
tool restarts that clock and publishes every (publisher, topic) pair
once more, which earns again any permit the last flush took (the first
window's were earned in set-up). Each rate's window follows a warm
window at that rate, as a benchmark run's does
(``harness.Session.warm_window``); ``--warm-seconds 0`` leaves it out,
as the benchmark did before it had one.

For a study of the spread between windows, repeat one rate:

    python3 benchmark/sweep.py --workload p2p_1m.knee80 --seed 7 \
        --loads 2000,2000,2000,2000 --seconds 30 --trace-seconds 0
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--loads", required=True,
                    help="offered msgs/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--trace-seconds", type=float, default=3.0,
                    help="the traced window after each rate; 0: none")
    ap.add_argument("--warm-seconds", type=float, default=None,
                    help="the warm window before each rate's; 0: none "
                    "(default: the benchmark's)")
    args = ap.parse_args()
    for p in (HERE, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import registry

    cell = registry.load_cell(ROOT, args.workload)
    import harness

    layout = harness.cpu_layout(cell["traffic"])
    import evaluate
    import run
    import numpy as np

    import readers
    import stats

    run.enable_compile_cache()
    warm_s = harness.WARM_S if args.warm_seconds is None else args.warm_seconds
    rows = []
    with harness.Session(cell, args.seed, t_proc=T_PROC, layout=layout,
                         traced=args.trace_seconds > 0) as s:
        key = "rate_per_s"
        for i, load in enumerate(float(x) for x in args.loads.split(",")):
            if i:
                s.server._last_permit_flush = time.monotonic()
                s.warm_permits()
            if warm_s > 0:
                s.warm_window(warm_s, **{key: load})
            out = s.window(args.seconds, False, **{key: load})
            res, lines, checks = evaluate.evaluate(
                cell, out, args.seconds, False, s.devices[0])
            for line in lines:
                harness.log(f"[{load}] {line}")
            d = evaluate.deliveries(out)
            in_window = int(((d["arrive"] >= 0)
                             & (d["arrive"] <= out["t_end"])).sum())
            late = out["sent"] - out["sched"]
            hists = evaluate.lane_hists(out)
            t = None
            if args.trace_seconds > 0:
                s.server._last_permit_flush = time.monotonic()
                s.warm_permits()
                tw = s.window(args.trace_seconds + 0.5, True,
                              trace_s=args.trace_seconds, **{key: load})
                t = tw["trace"]
            sizes = np.asarray(out["sizes"], np.int64)
            row = {
                key: load,
                "sent_per_s": len(out["msg"]) / args.seconds,
                "max_ms": (float(d["lat_ns"][d["found"]].max()) / 1e6
                           if d["found"].any() else None),
                "delivered_over_offered": in_window / max(1, d["due"]),
                "idle_pct": (100.0 * (1 - t["busy_s"] / t["window_s"])
                             if t else None),
                "step_ms": (t["step_device_s"] / t["step_launches"] * 1e3
                            if t and t["step_launches"] else None),
                "lane_batch_mean": float(sizes.mean()) if len(sizes) else 0,
                "lane_batch_buckets": {
                    int(b): int(n) for b, n in zip(*np.unique(
                        np.maximum(64, 2 ** np.ceil(np.log2(
                            np.maximum(sizes, 1)))), return_counts=True))},
                **{name: readers.hist_mean_ms(hists[h])
                   for name, h in readers.LANE_SEGMENTS.items()},
                "lane_total_mean_ms": readers.hist_mean_ms(
                    hists[readers.LANE_TOTAL]),
                "launches": out["end"]["launches"]
                - out["start"]["launches"],
                "gc_broker": out["gc"],
                "permit_age_s": out["permit_age_s"],
                "gen_late_p95_ms": stats.percentile(late, 0.95) / 1e6,
                "first_200ms_late_max_ms": float(late[
                    out["sched"] < out["t0"] + 200_000_000].max()) / 1e6,
                "correct": res["correct"],
                "checks": {k: v["value"] for k, v in res["checks"].items()
                           if v["value"]},
            }
            for m, v in res["metrics"].items():
                row[m] = v["value"]
            rows.append(row)
            print(json.dumps(row), flush=True)
    cols = [key, "sent_per_s", "p50_ms", "p95_ms", "max_ms",
            "delivered_over_offered", "idle_pct", "step_ms",
            "lane_batch_mean", "lane_batch_buckets", "correct"]
    print("| " + " | ".join(cols) + " |")
    print("|" + "---|" * len(cols))
    for r in rows:
        print("| " + " | ".join(str(r.get(c)) for c in cols) + " |")


if __name__ == "__main__":
    main()

"""The arithmetic of the per-layer metrics. Each ``metrics/<name>.py``
names one of these as its ``read``; a reader that finds nothing to read
returns None and the metric is left out of the result."""

from __future__ import annotations

import stats

# The lane from socket read to flush as the program times it, every lane
# message on one clock: six segments in path order, each a histogram of
# the broker's, and their whole. The six sums add up to the whole's.
LANE_SEGMENTS = {
    "host_park_mean_ms": "latency.native.lane_park",
    "lane_wait_mean_ms": "latency.lane.wait",
    "router_submit_mean_ms": "latency.lane.submit",
    "lane_collect_wait_mean_ms": "latency.lane.device",
    "router_decode_mean_ms": "latency.lane.decode",
    "host_deliver_mean_ms": "latency.native.lane_deliver",
}
LANE_TOTAL = "latency.native.lane_total"
LANE_HISTS = (*LANE_SEGMENTS.values(), LANE_TOTAL)


def hist_mean_ms(delta: dict):
    """Mean of a windowed histogram delta in ms; None when the window
    observed nothing."""
    if delta["count"] <= 0:
        return None
    return delta["sum_ns"] / delta["count"] / 1e6


def _segment(ctx, name: str):
    return hist_mean_ms(ctx["lane_hists"][LANE_SEGMENTS[name]])


def host_park_mean_ms(ctx):
    return _segment(ctx, "host_park_mean_ms")


def lane_wait_mean_ms(ctx):
    return _segment(ctx, "lane_wait_mean_ms")


def router_submit_mean_ms(ctx):
    return _segment(ctx, "router_submit_mean_ms")


def lane_collect_wait_mean_ms(ctx):
    return _segment(ctx, "lane_collect_wait_mean_ms")


def router_decode_mean_ms(ctx):
    return _segment(ctx, "router_decode_mean_ms")


def host_deliver_mean_ms(ctx):
    return _segment(ctx, "host_deliver_mean_ms")


def gen_late_p95_ms(ctx):
    late = ctx.get("gen_late_ns")
    if late is None or not len(late):
        return None
    return stats.percentile(late, 0.95) / 1e6


def idle_share(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_ms(ctx):
    t = ctx.get("trace")
    if not t or not t["step_launches"]:
        return None
    return t["step_device_s"] / t["step_launches"] * 1e3


def router_step_roofline(ctx):
    """Least time the chip could take for the launches' real topics
    (their trie-walk bytes over peak HBM bandwidth) over the router
    step's device time, per launch, in percent."""
    t = ctx.get("trace")
    per_launch = ctx.get("bytes_per_launch")
    if not t or not t["step_launches"] or not per_launch:
        return None
    least_s = per_launch / ctx["peak"]()["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["step_device_s"] / t["step_launches"])

"""Bench-artifact schema lint (ISSUE 17 satellite): every committed
BENCH_r*.json must carry the fields the bench exists to capture, so a
future run can't silently drop them the way the round-5 run dropped
``kernel_platform`` (renamed to ``platform`` by _compose and discarded).

The artifact wrapper is driver-written: ``{"n", "cmd", "rc", "tail",
"parsed"}`` with the bench's own cumulative JSON line under ``parsed``.

Grandfathering is explicit and frozen: rounds that PREDATE a field are
exempt from it (r02 and r04 predate the probe capture); everything from
r06 on must carry the full set. The r01, r03 and r05 records were
deleted in PR 21: r01 was taken through an access layer and with a
kernel that no longer exist, r03 and r05 are CPU runs of device
sections. Device numbers are not measured until the ledger-grade
benchmark lands.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fields → first round REQUIRED to carry them
PROBE_KEYS_SINCE = 5          # probe_ok / probe_log landed in r05
PLATFORM_KEY_SINCE = 6        # kernel_platform retention (this issue)
TENM_KEYS_SINCE = 6           # the standing 10M capture + sharded arm
KERNEL_TELEMETRY_KEYS_SINCE = 7   # ISSUE 19: stage percentiles + the
#                                   counters-overhead interleaved pair

TENM_KEYS = (
    "tenm_platform",
    "tenm_build_s",
    "tenm_device_gib",
    "tenm_topics_per_sec",
    "tenm_sync_p99_ms",
)
SHARDED_ARM_KEYS = (
    "tenm_sharded_shards",
    "tenm_sharded_mesh",
    "tenm_sharded_topics_per_sec",
    "tenm_sharded_sync_p99_ms",
)
KERNEL_TELEMETRY_KEYS = (
    "kernel_submit_p50_us",
    "kernel_submit_p99_us",
    "kernel_step_p50_us",
    "kernel_step_p99_us",
    "kernel_decode_p50_us",
    "kernel_decode_p99_us",
    "kernel_counters_on_topics_per_sec",
    "kernel_counters_off_topics_per_sec",
    "kernel_counters_overhead_frac",
    "kernel_counters_within_2pct_budget",
)


def _artifacts():
    out = []
    for name in sorted(os.listdir(REPO)):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", name)
        if m:
            out.append((int(m.group(1)), os.path.join(REPO, name)))
    return out


ARTIFACTS = _artifacts()


def test_artifacts_exist():
    assert ARTIFACTS, "no BENCH_r*.json artifacts committed"


@pytest.mark.parametrize(
    "rnd,path", ARTIFACTS, ids=[f"r{r:02d}" for r, _ in ARTIFACTS])
def test_bench_artifact_schema(rnd, path):
    with open(path) as f:
        wrapper = json.load(f)
    for key in ("n", "cmd", "rc", "tail", "parsed"):
        assert key in wrapper, f"r{rnd:02d}: wrapper missing {key!r}"
    assert wrapper["n"] == rnd, (
        f"r{rnd:02d}: wrapper n={wrapper['n']} != filename round")
    parsed = wrapper["parsed"] or {}

    if rnd >= PROBE_KEYS_SINCE:
        assert "probe_ok" in parsed, f"r{rnd:02d}: missing probe_ok"
        assert "probe_log" in parsed, f"r{rnd:02d}: missing probe_log"

    if rnd >= PLATFORM_KEY_SINCE:
        assert "kernel_platform" in parsed, (
            f"r{rnd:02d}: missing kernel_platform — _compose must keep "
            f"the raw capture key alongside the 'platform' label")
        # probe resolution: ok, or a bounded-degradation reason — a
        # hang (probe_ok=false with no recorded reason) is the r05
        # failure mode this issue retired
        if not parsed.get("probe_ok"):
            assert parsed.get("probe_degraded_reason"), (
                f"r{rnd:02d}: probe_ok is false without a "
                f"probe_degraded_reason")

    if rnd >= TENM_KEYS_SINCE:
        for key in TENM_KEYS + SHARDED_ARM_KEYS:
            assert key in parsed, f"r{rnd:02d}: missing {key}"

    if rnd >= KERNEL_TELEMETRY_KEYS_SINCE:
        for key in KERNEL_TELEMETRY_KEYS:
            assert key in parsed, f"r{rnd:02d}: missing {key}"

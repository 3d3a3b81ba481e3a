"""The connected-vehicle fleet workload (BASELINE configs 2/3).

A 7-level topic tree, ``fleet/f{fleet}/vehicle/v{id}/part/p{part}/m{metric}``,
subscribed with ~10% single-level ``+`` and a few percent ``#`` filters,
and publish topics that instantiate subscribed filters — the
``emqx_broker_bench`` pattern. Shared by ``bench.py`` and
``chip_smoke.py``; everything is drawn from the caller's seeded ``rng``.
"""

from __future__ import annotations

import numpy as np


def build_filters(n: int, rng: np.random.Generator) -> list[str]:
    """Vehicle-fleet topic tree, 7 levels deep, ~10% '+' wildcards,
    a few percent '#' — the BASELINE config 2/3 shape."""
    n_vehicles = max(1000, n // 2)
    filters = []
    kinds = rng.random(n)
    vids = rng.integers(0, n_vehicles, n)
    fleets = rng.integers(0, 512, n)
    metrics = rng.integers(0, 16, n)
    parts = rng.integers(0, 8, n)
    for i in range(n):
        v, fl, m, p = vids[i], fleets[i], metrics[i], parts[i]
        k = kinds[i]
        if k < 0.80:      # exact 7-level
            f = f"fleet/f{fl}/vehicle/v{v}/part/p{p}/m{m}"
        elif k < 0.90:    # single-level '+'
            f = f"fleet/f{fl}/vehicle/+/part/p{p}/m{m}"
        elif k < 0.95:
            f = f"fleet/f{fl}/vehicle/v{v}/part/+/m{m}"
        elif k < 0.98:    # multi-level '#'
            f = f"fleet/f{fl}/vehicle/v{v}/#"
        else:
            f = f"fleet/+/vehicle/v{v}/part/p{p}/#"
        filters.append(f)
    return filters


def make_topics(live: list[str], rng: np.random.Generator, count: int,
                n_vehicles: int) -> list[str]:
    """Publish into the subscribed tree (emqx_broker_bench shape):
    instantiate a random subscribed filter's wildcards with concrete
    words."""
    picks = rng.integers(0, len(live), count)
    v = rng.integers(0, n_vehicles, count)
    p = rng.integers(0, 8, count)
    m = rng.integers(0, 16, count)
    fl = rng.integers(0, 512, count)
    topics = []
    for i in range(count):
        ws = live[picks[i]].split("/")
        out = []
        for j, w in enumerate(ws):
            if w == "+":
                out.append(
                    f"v{v[i]}" if j == 3 else f"p{p[i]}" if j == 5 else f"f{fl[i]}"
                )
            elif w == "#":
                out.extend([f"part/p{p[i]}", f"m{m[i]}"][: 7 - j])
                break
            else:
                out.append(w)
        topics.append("/".join(out))
    return topics

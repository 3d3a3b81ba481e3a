"""Counters-layout lint (ISSUE 19 satellite): the kernel packs its
per-batch counters as a positional int32 vector, and the host decoder
reads it back positionally — there is no schema on the wire. The field
order is declared ONCE in ops/trie_match.py; observe/device_metrics.py
carries a literal copy. This lint is the only thing holding the two in
parity, so a field added to one module without the other fails HERE,
not as silently-swapped telemetry."""

import numpy as np
import pytest

from emqx_tpu.observe import device_metrics as dm
from emqx_tpu.ops import trie_match as tm


def test_counter_fields_parity():
    # the load-bearing assert: packer and decoder share one layout
    assert tm.KERNEL_COUNTER_FIELDS == dm.KERNEL_COUNTER_FIELDS
    assert len(set(tm.KERNEL_COUNTER_FIELDS)) == \
        len(tm.KERNEL_COUNTER_FIELDS)


def test_pack_decode_round_trip():
    # distinct sentinels per field: a swapped position cannot cancel
    vals = {n: 100 + i for i, n in enumerate(tm.KERNEL_COUNTER_FIELDS)}
    raw = tm.pack_counters(**vals)
    assert raw.shape == (len(tm.KERNEL_COUNTER_FIELDS),)
    kc = dm.KernelCounters(raw)
    assert kc.n_shards == 1
    for n, v in vals.items():
        assert kc.value(n) == v


def test_pack_decode_round_trip_sharded():
    S = 4
    vals = {n: np.arange(S, dtype=np.int32) * (i + 1)
            for i, n in enumerate(tm.KERNEL_COUNTER_FIELDS)}
    raw = tm.pack_counters(**vals)
    assert raw.shape == (S, len(tm.KERNEL_COUNTER_FIELDS))
    kc = dm.KernelCounters(raw)
    assert kc.n_shards == S
    for i, n in enumerate(tm.KERNEL_COUNTER_FIELDS):
        assert kc.field(n).tolist() == (np.arange(S) * (i + 1)).tolist()
    # fold rule: peaks max over shards, the rest sum
    assert kc.value("frontier_peak") == int(vals["frontier_peak"].max())
    assert kc.value("probe_iters") == int(vals["probe_iters"].sum())


def test_walk_levels_declared_in_both_and_summed():
    # the level-loop bound's counter, in both declarations (their
    # parity is test_counter_fields_parity's), folds as a sum over
    # shards, not a peak
    assert "walk_levels" in tm.KERNEL_COUNTER_FIELDS
    assert "walk_levels" not in dm._PEAK_FIELDS
    vals = {n: np.zeros(2, np.int32) for n in tm.KERNEL_COUNTER_FIELDS}
    vals["walk_levels"] = np.array([3, 3], np.int32)
    kc = dm.KernelCounters(tm.pack_counters(**vals))
    assert kc.field("walk_levels").tolist() == [3, 3]
    assert kc.value("walk_levels") == 6


def test_pack_counters_rejects_drifted_field_set():
    vals = {n: 1 for n in tm.KERNEL_COUNTER_FIELDS}
    with pytest.raises(TypeError):
        tm.pack_counters(**{**vals, "bogus_field": 1})
    missing = dict(vals)
    missing.pop(tm.KERNEL_COUNTER_FIELDS[0])
    with pytest.raises(TypeError):
        tm.pack_counters(**missing)


def test_decoder_rejects_wrong_width():
    with pytest.raises(ValueError):
        dm.KernelCounters(np.zeros(len(dm.KERNEL_COUNTER_FIELDS) + 1,
                                   np.int32))


# -- real-kernel spot checks: the counters mean what their names say ------

def _match_stats(filters, topics, K=32, max_levels=8):
    from emqx_tpu.router.index import TrieIndex

    idx = TrieIndex(max_levels=max_levels)
    idx.load(filters)
    dev = tm.device_trie(idx.ensure())
    tok, lens, sysf, too_long = idx.tokenize(topics)
    assert not too_long
    cand, overflow, mstats = tm.match_batch(
        dev, np.asarray(tok), np.asarray(lens), np.asarray(sysf), K=K)
    return (np.asarray(cand), np.asarray(overflow),
            {k: int(v) for k, v in mstats.items()})


def test_kernel_counters_sane_batch():
    filters = ["a/+/c", "a/b/#", "d/e", "a/b/c"]
    cand, overflow, st = _match_stats(filters, ["a/b/c", "d/e", "x/y"])
    n_matched = int(np.sum(cand >= 0))
    assert st["cand_pre"] == n_matched == 4
    assert st["overflow_rows"] == 0
    # 3 matches on row 0 → the frontier held at least 2 live walkers
    assert st["frontier_peak"] >= 2
    # every resolved exact edge costs at least one probe iteration
    assert st["probe_iters"] >= 1
    # the deepest topic has 3 levels: the walk ran levels 0..3
    assert st["walk_levels"] == 4


def test_kernel_counters_overflow_rows():
    # a full binary exact/plus fan doubles the frontier every level;
    # K=2 cannot hold the 4-walker front at depth 3 → overflow
    filters = ["a/b/c/d", "a/b/c/+", "a/b/+/d", "a/b/+/+",
               "a/+/c/d", "a/+/c/+", "a/+/+/d", "a/+/+/+"]
    cand, overflow, st = _match_stats(filters, ["a/b/c/d"], K=2)
    assert bool(overflow[0])
    assert st["overflow_rows"] == int(np.sum(overflow)) == 1
    assert st["frontier_peak"] == 2     # clamped at K

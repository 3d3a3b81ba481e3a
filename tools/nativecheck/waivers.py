"""nativecheck waivers: deliberate, justified exceptions to the rules.

Each entry names the rule, the exact finding site key, and a one-line
justification. A waiver that matches no live finding FAILS the check
(stale-waiver hygiene): when the code stops violating, the waiver must
be deleted, so this file can never silently rot into a blanket
allowlist. Keep justifications honest — they are the documented
contract for why the violation is the design.
"""

WAIVERS = [
    # -- plane: the durable store's fsync contract ---------------------------
    # FlushDirty orders every socket write of a read batch BEHIND the
    # durable batch append + policy msync (host.cc round 10): a QoS1
    # PUBACK on the wire must imply the message is on disk, so the
    # poll thread paying the (batched, once-per-flush) msync IS the
    # durability design — the 120k-msyncs wedge this analyzer exists
    # to prevent was PER-ENTRY consumes, which now batch per record on
    # Python threads.
    {"rule": "plane", "site": "store.h:SyncSeg",
     "why": "PUBACK-after-fsync durability contract: one batched msync "
            "per flush on the poll thread is the round-10 design"},
    # AppendFrame rolls to a fresh segment when the active one fills:
    # an open/ftruncate/mmap on the poll thread, amortized over a whole
    # segment (default 4 MB) of appends.
    {"rule": "plane", "site": "store.h:Roll",
     "why": "segment roll (open+ftruncate+mmap) amortized over a whole "
            "segment of batched appends; same contract as SyncSeg"},

    # -- ladder: receivers of already-admitted publishes ---------------------
    # The trunk receiver cannot punt a publish that already left its
    # origin node (the sender ran the ladder); FanOut degrades its
    # cross-shard legs per-destination through the RingRoom re-check
    # instead (host.cc TrunkFanOut comment).
    {"rule": "ladder", "site": "host.cc:TrunkFanOut->FanOut",
     "why": "trunk receiver: the PUBLISHING node ran the ladder; FanOut "
            "degrades per-destination via its RingRoom re-check"},
    # Ring consumers apply entries the producer shard already admitted
    # (ShardAdmit ran before the entry was shipped).
    {"rule": "ladder", "site": "host.cc:ApplyShardBatch->TrunkEnqueue",
     "why": "ring consumer: the producing shard ran ShardAdmit before "
            "shipping the trunk-forward entry"},
]

# The declared lock-acquisition order (rule 8, round 17). Every edge
# the analyzer OBSERVES in the global graph (lock_guard scopes + `with
# self._lock` regions, call-graph propagated across both languages)
# must be declared here; every edge declared here must still be
# observed (stale edges fail, the waiver-hygiene discipline). The
# chain below is the PR 9 _durable_token docstring, now enforced.
# Reentrant self-acquisition of an RLock is the lock's own semantics
# and needs no entry; a self-edge on a plain Lock always fails.
LOCK_ORDER = [
    # subscribe events fold shared-group state, then reconcile the
    # C++ install under the mirror lock (_reconcile_shared)
    {"order": "_shared_lock < _mirror_lock",
     "why": "_on_shared_event holds _shared_lock across "
            "_reconcile_shared, which takes _mirror_lock for the punt "
            "refcounts"},
    # the sub-event fold runs whole under the reentrant _mirror_lock
    # and mints durable tokens inside it (_durable_token)
    {"order": "_mirror_lock < _durable_lock",
     "why": "_on_sub_event holds _mirror_lock across "
            "_on_sub_event_locked -> _durable_token, which writes the "
            "reverse map under _durable_lock; never acquire "
            "_mirror_lock while holding _durable_lock"},
    # kind-10 folds resolve closed-conn info for disconnected sessions
    {"order": "_durable_lock < _closed_lock",
     "why": "_on_durable_locked (@locked(_durable_lock)) resolves "
            "conninfo through _conninfo_for, which reads _closed_conns "
            "under _closed_lock"},
    # the span fold attributes ingress spans to (possibly just-closed)
    # publisher conns
    {"order": "_tele_lock < _closed_lock",
     "why": "_on_spans holds _tele_lock across _conninfo_for's "
            "_closed_conns read"},
    # a lane drain revokes the drained topics' permits, so closing the
    # lane forgets the Python grant record under _permit_lock
    {"order": "_lane_lock < _permit_lock",
     "why": "_lane_drained (called under _lane_lock) clears _granted "
            "under _permit_lock; never take _lane_lock while holding "
            "_permit_lock"},
]

"""The load generator: publisher and receiver processes.

They run apart from the broker's process, on CPU cores of their own,
and import nothing of the program and nothing of JAX: they speak MQTT
3.1.1 over TCP with the few packets below. ``run.py`` starts them with
the ``spawn`` method; a process that finds ``jax`` imported refuses to
run.

Every PUBLISH payload is ``[u64 message id][i64 scheduled send time, ns
of CLOCK_MONOTONIC][u32 topic index]`` and then filler that is a slice of
a fixed byte pattern keyed by the id, so a receiver can check each
delivery's topic and every payload byte. An open-loop latency runs from
the scheduled send time to the read at the subscriber's socket; both
clocks are the host's CLOCK_MONOTONIC, which is system-wide.

Shared counters (one int64 array): slot ``2*r`` counts receiver
``r``'s window deliveries, ``2*r+1`` its deliveries of messages with
the warm-up mark (``WARM_BIT``: the permit warm-up's and the warm
windows'), which it neither records nor checks.
"""

from __future__ import annotations

import gc
import os
import resource
import selectors
import socket
import struct
import sys
import time
from array import array

import numpy as np

HEAD = struct.Struct("<QqI")
FILL = bytes(range(256)) * 3
FILL_KEY = 251
WARM_BIT = 1 << 62
PROC_SHIFT = 40
STOOD_STILL_NS = 200_000_000


# -- MQTT 3.1.1 wire --------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _str(s: bytes) -> bytes:
    return struct.pack(">H", len(s)) + s


def connect_packet(clientid: str, keepalive: int = 300) -> bytes:
    body = _str(b"MQTT") + bytes([4, 0x02]) + struct.pack(">H", keepalive)
    body += _str(clientid.encode())
    return b"\x10" + _varint(len(body)) + body


def subscribe_packet(pid: int, filters) -> bytes:
    body = struct.pack(">H", pid)
    for f in filters:
        body += _str(f.encode()) + b"\x00"
    return b"\x82" + _varint(len(body)) + body


def publish_prefix(topic: bytes, payload_len: int) -> bytes:
    """Fixed header + topic of a QoS 0 PUBLISH whose payload follows."""
    return b"\x30" + _varint(2 + len(topic) + payload_len) + _str(topic)


def payload(msg_id: int, sched_ns: int, tidx: int, size: int) -> bytes:
    off = msg_id % FILL_KEY
    return HEAD.pack(msg_id, sched_ns, tidx) + FILL[off:off + size - HEAD.size]


def read_packet(sock: socket.socket, buf: bytearray) -> tuple[int, bytes]:
    """Blocking read of one whole packet: (first byte, body)."""
    while True:
        got = split_packet(buf)
        if got is not None:
            return got
        data = sock.recv(65536)
        if not data:
            raise ConnectionError("broker closed the connection")
        buf += data


def split_packet(buf: bytearray):
    if len(buf) < 2:
        return None
    n, shift, i = 0, 0, 1
    while True:
        if i >= len(buf):
            return None
        b = buf[i]
        n |= (b & 0x7F) << shift
        i += 1
        if not b & 0x80:
            break
        shift += 7
    if len(buf) < i + n:
        return None
    first, body = buf[0], bytes(buf[i:i + n])
    del buf[:i + n]
    return first, body


def open_client(port: int, clientid: str, rcvbuf: int = 0) -> socket.socket:
    s = socket.create_connection(("127.0.0.1", port))
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    if rcvbuf:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.sendall(connect_packet(clientid))
    first, body = read_packet(s, bytearray())
    if first != 0x20 or len(body) < 2 or body[1] != 0:
        raise ConnectionError(f"{clientid}: CONNACK {first:#x} {body!r}")
    return s


# -- process plumbing -------------------------------------------------------

def _enter(cpus) -> None:
    if "jax" in sys.modules:
        raise RuntimeError("a load-generator process imported jax")
    if cpus:
        os.sched_setaffinity(0, cpus)


def _switches() -> dict:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return {"voluntary": r.ru_nvcsw, "involuntary": r.ru_nivcsw}


class GcClock:
    """Counts the cyclic collector's pauses in this process."""

    def __init__(self) -> None:
        self.n = 0
        self.total_ns = 0
        self.max_ns = 0
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info) -> None:
        if phase == "start":
            self._t0 = time.monotonic_ns()
        else:
            d = time.monotonic_ns() - self._t0
            self.n += 1
            self.total_ns += d
            self.max_ns = max(self.max_ns, d)

    def reset(self) -> None:
        self.n = self.total_ns = self.max_ns = 0

    def as_dict(self) -> dict:
        return {"n": self.n, "total_ms": self.total_ns / 1e6,
                "max_ms": self.max_ns / 1e6}


# -- receivers --------------------------------------------------------------

def receiver_main(ctl, spec: dict, counters) -> None:
    """Hold ``spec["subs"]`` subscriber connections; record every
    delivery as (subscriber, message id, read time, intact) and hand the
    records over on "report"."""
    _enter(spec.get("cpus"))
    gcc = GcClock()
    topics = spec["topics"]
    size = spec["payload_bytes"]
    base = spec["slot"] * 2
    socks = []
    for sub_idx, clientid, filters in spec["subs"]:
        s = open_client(spec["port"], clientid, rcvbuf=4 << 20)
        s.sendall(subscribe_packet(1, filters))
        first, body = read_packet(s, bytearray())
        if first != 0x90 or any(c >= 0x80 for c in body[2:]):
            raise ConnectionError(f"{clientid}: SUBACK {first:#x} {body!r}")
        s.setblocking(False)
        socks.append((s, sub_idx))
    sel = selectors.DefaultSelector()
    for s, sub_idx in socks:
        sel.register(s, selectors.EVENT_READ, sub_idx)
    sel.register(ctl, selectors.EVENT_READ, None)
    ctl.send(("ready", len(socks)))
    rest = {sub_idx: b"" for _, sub_idx in socks}   # partial packets
    rec_sub, rec_msg, rec_t, rec_ok = (array("i"), array("q"), array("q"),
                                       array("b"))
    warm = 0
    window = 0
    unpack = HEAD.unpack_from
    # (start, length) of each stretch in which this loop, which wakes at
    # least every 50 ms and never writes to the broker, stood still: a
    # stall of the whole host, not backpressure from the broker
    stood = array("q")
    prev = time.monotonic_ns()
    running = True
    while running:
        events = sel.select(timeout=0.05)
        now = time.monotonic_ns()
        if now - prev > STOOD_STILL_NS:
            stood.extend((prev, now - prev))
        prev = now
        for key, _ in events:
            sub_idx = key.data
            if sub_idx is None:
                msg = ctl.recv()
                if msg == "stop":
                    running = False
                elif msg == "reset_gc":
                    gcc.reset()
                    stood = array("q")
                elif msg == "report":
                    ctl.send({"sub": rec_sub.tobytes(),
                              "msg": rec_msg.tobytes(),
                              "t": rec_t.tobytes(), "ok": rec_ok.tobytes(),
                              "warm": warm, "gc": gcc.as_dict(),
                              "stood_still": stood.tolist()})
                    rec_sub, rec_msg, rec_t, rec_ok = (
                        array("i"), array("q"), array("q"), array("b"))
                continue
            try:
                data = key.fileobj.recv(1 << 20)
            except BlockingIOError:
                continue
            now = time.monotonic_ns()
            if not data:
                raise ConnectionError(f"subscriber {sub_idx} closed")
            if rest[sub_idx]:
                data = rest[sub_idx] + data
            pos, end_all = 0, len(data)
            while end_all - pos >= 2:
                n, shift, i = 0, 0, pos + 1
                while i < end_all:
                    b = data[i]
                    n |= (b & 0x7F) << shift
                    i += 1
                    if not b & 0x80:
                        break
                    shift += 7
                else:
                    break
                end = i + n
                if end > end_all:
                    break
                first = data[pos]
                pos = end
                if first >> 4 != 3:
                    continue
                tl = (data[i] << 8) | data[i + 1]
                topic = data[i + 2:i + 2 + tl]
                p = i + 2 + tl + (2 if (first >> 1) & 3 else 0)
                if end - p < HEAD.size:
                    rec_sub.append(sub_idx)
                    rec_msg.append(-1)
                    rec_t.append(now)
                    rec_ok.append(0)
                    continue
                mid, _sched, tidx = unpack(data, p)
                if mid & WARM_BIT:
                    warm += 1
                    continue
                off = mid % FILL_KEY
                ok = (end - p == size and tidx < len(topics)
                      and topic == topics[tidx]
                      and data[p + HEAD.size:end]
                      == FILL[off:off + size - HEAD.size])
                rec_sub.append(sub_idx)
                rec_msg.append(mid)
                rec_t.append(now)
                rec_ok.append(1 if ok else 0)
                window += 1
            rest[sub_idx] = data[pos:]
            counters[base] = window
            counters[base + 1] = warm
    for s, _ in socks:
        s.close()


# -- publishers -------------------------------------------------------------

def publisher_main(ctl, spec: dict) -> None:
    """Own ``spec["conns"]`` publisher connections, each with its topic
    pool; warm every (connection, topic) permit, then run each window's
    open loop (a warm window's or a measured one's) at the rate it is
    given and report what was sent and when."""
    _enter(spec.get("cpus"))
    gcc = GcClock()
    topics = spec["topics"]
    size = spec["payload_bytes"]
    proc = spec["proc"]
    pools = [np.asarray(pool, np.int64) for _, pool in spec["conns"]]
    socks = [open_client(spec["port"], cid) for cid, _ in spec["conns"]]
    prefix = {}
    for pool in pools:
        for t in pool.tolist():
            prefix[t] = publish_prefix(topics[t], size)
    ctl.send(("ready", len(socks)))
    while True:
        cmd = ctl.recv()
        if cmd[0] == "warm":
            n = 0
            for c, (s, pool) in enumerate(zip(socks, pools)):
                out = []
                for t in pool.tolist():
                    mid = WARM_BIT | (proc << 20) | n
                    out.append(prefix[t] + payload(mid, 0, t, size))
                    n += 1
                s.sendall(b"".join(out))
            ctl.send(("warmed", n))
        elif cmd[0] == "go":
            _, t0, seconds, rate, warm = cmd
            gcc.reset()
            sw0 = _switches()
            res = _open_loop(dict(spec, rate_per_s=rate), socks, pools,
                             prefix, t0, seconds, warm)
            sw1 = _switches()
            res["gc"] = dict(gcc.as_dict(), **{
                f"{k}_switches": sw1[k] - sw0[k] for k in sw1})
            ctl.send(res)
        elif cmd[0] == "stop":
            break
    for s in socks:
        s.close()


def open_schedule(spec: dict, n_pools: int, seconds: float):
    """Evenly spaced sends: connection ``g`` of ``C`` sends at
    ``(g + phase + k*C) / rate``, so the merged stream of all C
    connections is evenly spaced too, and every seed offers the same
    arrivals shifted by its ``phase`` (drawn from the seed, one for the
    whole run). Topics are uniform over each connection's pool. Returns
    (offset ns, connection, pool position) sorted by offset."""
    rng = np.random.default_rng(spec["seed"])
    rate, C = spec["rate_per_s"], spec["n_conns"]
    gap = C * 1e9 / rate
    offs, conns = [], []
    for c, g in enumerate(spec["conn_index"][:n_pools]):
        first = (g + spec["phase"]) * 1e9 / rate
        k = np.arange(max(0, int(np.ceil((seconds * 1e9 - first) / gap))))
        offs.append((first + k * gap).astype(np.int64))
        conns.append(np.full(len(k), c, np.int64))
    off = np.concatenate(offs)
    conn = np.concatenate(conns)
    order = np.argsort(off, kind="stable")
    off, conn = off[order], conn[order]
    pos = rng.random(len(off))
    return off, conn, pos


def _open_loop(spec, socks, pools, prefix, t0, seconds, warm) -> dict:
    """One window's sends; a warm window's carry the warm-up mark."""
    size = spec["payload_bytes"]
    base = (spec["proc"] << PROC_SHIFT) | (WARM_BIT if warm else 0)
    off, conn, pos = open_schedule(spec, len(pools), seconds)
    n = len(off)
    tidx = np.empty(n, np.int64)
    for c, pool in enumerate(pools):
        m = conn == c
        tidx[m] = pool[(pos[m] * len(pool)).astype(np.int64)]
    sched = t0 + off
    sent = np.empty(n, np.int64)
    tl, cl, sl = tidx.tolist(), conn.tolist(), sched.tolist()
    mono = time.monotonic_ns
    sleep = time.sleep
    for k in range(n):
        due = sl[k]
        ahead = due - mono()
        if ahead > 300_000:
            sleep((ahead - 200_000) / 1e9)
        while mono() < due:
            pass
        t = tl[k]
        socks[cl[k]].sendall(prefix[t] + payload(base | k, due, t, size))
        sent[k] = mono()
    return {"msg": (base | np.arange(n, dtype=np.int64)).tobytes(),
            "tidx": tidx.tobytes(), "sched": sched.tobytes(),
            "sent": sent.tobytes()}


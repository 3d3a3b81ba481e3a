"""ASan/TSan runs of the C++ connection host (SURVEY §5: the build's
planned stand-in for BEAM's share-nothing guarantees is C++-side
sanitizers — the host has a poll thread plus send/close entry points
callable from any thread, and an off-thread housekeeping path).

Each case compiles a sanitized variant of ``host.cc`` and drives it in a
SUBPROCESS with the sanitizer runtime LD_PRELOADed (a dlopen'd sanitized
.so needs its runtime loaded first). The driver exercises: accept,
byte-dribbled framing, concurrent cross-thread sends, close-during-send
races, and teardown. Any sanitizer report fails the run (halt_on_error)."""

import os
import subprocess
import sys

import pytest

_SAN_LIBS = {}
for _name, _lib in (("address", "libasan.so"), ("thread", "libtsan.so")):
    try:
        p = subprocess.run(["g++", f"-print-file-name={_lib}"],
                           capture_output=True, text=True).stdout.strip()
        if p and os.path.exists(p):
            _SAN_LIBS[_name] = p
    except OSError:
        pass


DRIVER = r"""
import os, socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)
N = 8

def connect_and_dribble(i):
    s = socket.create_connection(("127.0.0.1", host.port))
    # minimal MQTT CONNECT, dribbled byte-by-byte to stress the framer
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", 4) + b"c%%03d" %% i
    pkt = bytes([0x10, len(vh)]) + vh
    for b in pkt:
        s.sendall(bytes([b]))
        if i %% 3 == 0:
            time.sleep(0.001)
    return s

socks = []
conns = []
frames = 0
deadline = time.time() + 15

t_conns = [threading.Thread(target=lambda i=i: socks.append(
    connect_and_dribble(i))) for i in range(N)]
for t in t_conns: t.start()
for t in t_conns: t.join()

stop = threading.Event()
def blaster():
    # cross-thread sends against whatever connections exist (the
    # threading contract under test: send/close from non-poll threads)
    while not stop.is_set():
        for c in list(conns):
            host.send(c, b"\xd0\x00")       # PINGRESP
        time.sleep(0.0005)
blast = threading.Thread(target=blaster)
blast.start()

while frames < N and time.time() < deadline:
    for kind, conn, payload in host.poll(50):
        if kind == native.EV_OPEN:
            conns.append(conn)
        elif kind == native.EV_FRAME:
            frames += 1
            host.send(conn, b"\x20\x02\x00\x00")   # CONNACK
assert frames == N, f"framed {frames}/{N}"

# close-during-send race: keep the blaster running while closing
for c in conns[: N // 2]:
    host.close_conn(c)
time.sleep(0.05)
stop.set(); blast.join()
for s in socks:
    try: s.close()
    except OSError: pass
# drain close events, then teardown with the poll loop stopped
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK")
"""


# Round-4 fast-path coverage: enable_fast + sub/shared/punt control
# ops racing the poll thread, qos0/1 publish fan-out in C++ (TryFast /
# DeliverTo / TryFastPuback), native PUBACK consumption, permit churn,
# and close-during-delivery.
DRIVER_FASTPATH = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)

def mqtt_connect(cid):
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    return bytes([0x10, len(vh)]) + vh

def mqtt_publish(topic, payload, qos=0, pid=0):
    body = struct.pack(">H", len(topic)) + topic
    if qos:
        body += struct.pack(">H", pid)
    body += payload
    return bytes([0x30 | (qos << 1), len(body)]) + body

socks = [socket.create_connection(("127.0.0.1", host.port))
         for _ in range(3)]
ids = []
for i, s in enumerate(socks):
    s.sendall(mqtt_connect(b"f%%d" %% i))
deadline = time.time() + 15
framed = 0
while (len(ids) < 3 or framed < 3) and time.time() < deadline:
    for kind, conn, payload in host.poll(50):
        if kind == native.EV_OPEN:
            ids.append(conn)
        elif kind == native.EV_FRAME:
            framed += 1
            host.send(conn, b"\x20\x02\x00\x00")
assert len(ids) == 3 and framed == 3, (ids, framed)
sub1, sub2, pub = ids       # event order == connect order (one poller)

for c in ids:
    host.enable_fast(c, 4, 64)
host.sub_add(sub1, "fp/+", qos=1)
host.shared_add(7, sub2, "fp/+", 1, 0)  # qos1: acker exercises TryFastPuback
host.sub_add(1 << 50, "punted/#", 0, native.SUB_PUNT)
host.permit(pub, "fp/x")
host.permit(pub, "punted/y")

stop = threading.Event()
def control_churn():
    # thread-safe control plane hammering the poll thread's tables
    # (conn_idle_ms is deliberately NOT here: it is poll-thread-only —
    # TSan caught its conns_ walk racing Drop's erase when this driver
    # originally called it cross-thread)
    j = 0
    while not stop.is_set():
        host.sub_add(sub1, "churn/%%d" %% (j %% 7), 0, 0)
        host.sub_del(sub1, "churn/%%d" %% ((j + 3) %% 7))
        host.stats()
        if j %% 50 == 17:
            host.permits_flush()
            host.permit(pub, "fp/x")
            host.permit(pub, "punted/y")   # keep the punt-marker path live
        j += 1
        time.sleep(0.0002)
ctl = threading.Thread(target=control_churn)
ctl.start()

time.sleep(0.2)   # let the ops apply
N_MSG = 400
def blaster():
    for k in range(N_MSG):
        qos = k & 1
        socks[2].sendall(mqtt_publish(b"fp/x", b"p%%03d" %% k, qos,
                                      1 + (k %% 100)))
        socks[2].sendall(mqtt_publish(b"punted/y", b"q", 0, 0))
        if k == N_MSG // 2:
            socks[0].close()          # close a subscriber mid-delivery
        time.sleep(0.0002)
bl = threading.Thread(target=blaster)
bl.start()

# subscriber 2 acks native qos1 deliveries; the poll loop keeps running
def acker():
    buf = b""
    socks[1].settimeout(0.2)
    while not stop.is_set():
        try:
            chunk = socks[1].recv(4096)
        except (TimeoutError, OSError):
            continue
        if not chunk:
            return
        buf += chunk
        while len(buf) >= 2:
            ln = buf[1]
            if ln & 0x80 or len(buf) < 2 + ln:
                break
            frame, buf = buf[: 2 + ln], buf[2 + ln:]
            if frame[0] >> 4 == 3 and (frame[0] >> 1) & 3 == 1:
                tlen = (frame[2] << 8) | frame[3]
                pid = (frame[4 + tlen] << 8) | frame[5 + tlen]
                try:
                    socks[1].sendall(bytes([0x40, 2, pid >> 8, pid & 0xFF]))
                except OSError:
                    return
ack = threading.Thread(target=acker)
ack.start()

punts = 0
deadline = time.time() + 20
while time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == native.EV_FRAME:
            punts += 1            # punted/# frames come up verbatim
    host.conn_idle_ms(sub2)       # poll-thread-only query, on-thread here
    st = host.stats()
    # flush-to-re-permit gaps legitimately punt some fp/x messages;
    # this is a sanitizer drive, not a counting test — exit once every
    # exercised path has clearly run
    if (st["fast_in"] > N_MSG // 2 and st["shared_dispatch"] > 0
            and st["punts"] > 0 and st["native_acks"] > 0):
        break
bl.join()
time.sleep(0.3)
stop.set(); ctl.join(); ack.join()
st = host.stats()
assert st["fast_in"] > 0 and st["fast_out"] > 0, st
assert st["shared_dispatch"] > 0, st
assert st["native_acks"] > 0, st       # TryFastPuback ran
assert st["punts"] > 0, st             # the kSubPunt branch ran
assert punts > 0, "punted frames never forwarded"
for s in socks[1:]:
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st)
"""


# Round-5 device-lane coverage: EV_LANE records from TryFast's park
# path, lane_deliver blobs applied from a foreign thread (Enqueue →
# ApplyOp → LaneDeliver fan-out incl. the punt branch), set_lane
# toggles draining parked frames mid-traffic, and lane_backlog reads
# racing the poll thread.
DRIVER_LANE = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)

def mqtt_connect(cid):
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    return bytes([0x10, len(vh)]) + vh

def mqtt_publish(topic, payload, qos=0, pid=0):
    body = struct.pack(">H", len(topic)) + topic
    if qos:
        body += struct.pack(">H", pid)
    body += payload
    return bytes([0x30 | (qos << 1), len(body)]) + body

socks = [socket.create_connection(("127.0.0.1", host.port))
         for _ in range(2)]
ids = []
for i, s in enumerate(socks):
    s.sendall(mqtt_connect(b"l%%d" %% i))
deadline = time.time() + 15
framed = 0
while (len(ids) < 2 or framed < 2) and time.time() < deadline:
    for kind, conn, payload in host.poll(50):
        if kind == native.EV_OPEN:
            ids.append(conn)
        elif kind == native.EV_FRAME:
            framed += 1
            host.send(conn, b"\x20\x02\x00\x00")
assert len(ids) == 2 and framed == 2, (ids, framed)
sub, pub = ids

for c in ids:
    host.enable_fast(c, 4, 64)
host.sub_add(sub, "ln/+", 1, 0)
host.permit(pub, "ln/x")
host.set_lane(True)

stop = threading.Event()
lane_reqs = []
req_lock = threading.Lock()

def pump():
    # foreign-thread responder: builds blobs and enqueues them while
    # the poll thread keeps parking/draining entries
    k = 0
    while not stop.is_set():
        with req_lock:
            batch, lane_reqs[:] = lane_reqs[:], []
        if not batch:
            time.sleep(0.001)
            continue
        parts = [struct.pack("<I", len(batch))]
        for seq, topic in batch:
            k += 1
            if k %% 7 == 3:
                parts.append(struct.pack("<QBH", seq, 1, 0))  # punt
            else:
                f = b"ln/+"
                parts.append(struct.pack("<QBH", seq, 0, 1))
                parts.append(struct.pack("<H", len(f)))
                parts.append(f)
        host.lane_deliver(b"".join(parts))
pp = threading.Thread(target=pump)
pp.start()

def control_churn():
    j = 0
    while not stop.is_set():
        host.sub_add(sub, "churn/%%d" %% (j %% 5), 0, 0)
        host.sub_del(sub, "churn/%%d" %% ((j + 2) %% 5))
        host.lane_backlog()
        host.stats()
        if j %% 97 == 41:
            host.set_lane(False)   # drain parked frames mid-traffic
            host.set_lane(True)
            host.permit(pub, "ln/x")
        j += 1
        time.sleep(0.0002)
ctl = threading.Thread(target=control_churn)
ctl.start()

time.sleep(0.2)
N_MSG = 500
def blaster():
    for k in range(N_MSG):
        socks[1].sendall(mqtt_publish(b"ln/x", b"p%%03d" %% k, k & 1,
                                      1 + (k %% 100)))
        time.sleep(0.0002)
bl = threading.Thread(target=blaster)
bl.start()

drained = 0
deadline = time.time() + 20
while time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == 4:           # EV_LANE
            with req_lock:
                lane_reqs.append((conn, native.parse_lane(payload)[2]))
        elif kind == native.EV_FRAME:
            drained += 1        # punted/drained frames come up verbatim
    st = host.stats()
    if (st["lane_in"] > N_MSG // 4 and st["lane_out"] > 0
            and st["lane_punts"] > 0):
        break
bl.join()
time.sleep(0.3)
stop.set(); ctl.join(); pp.join()
st = host.stats()
assert st["lane_in"] > 0 and st["lane_out"] > 0, st
assert st["lane_punts"] > 0, st
for s in socks:
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st)
"""


# Round-7 WebSocket coverage: the RFC6455 plane (ws.h + host.cc) under
# the sanitizers — upgrade handshakes (incl. a rejected one), masked
# frame decode with in-place unmasking, byte-dribbled and fragmented
# frames, ping/pong, close echo, fast-path delivery ONTO a ws conn
# (egress wrapping), cross-thread sends, and close-during-traffic.
DRIVER_WS = r"""
import os, socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)
wsp = host.listen_ws()

def mask(payload, key=b"\x11\x22\x33\x44"):
    return bytes(b ^ key[i %% 4] for i, b in enumerate(payload))

def frame(op, payload, fin=True, key=b"\x11\x22\x33\x44"):
    h = bytearray([(0x80 if fin else 0) | op])
    n = len(payload)
    if n < 126:
        h.append(0x80 | n)
    else:
        h.append(0x80 | 126); h += struct.pack(">H", n)
    return bytes(h) + key + mask(payload, key)

def upgrade(s, dribble=False):
    req = (b"GET /mqtt HTTP/1.1\r\nHost: x\r\nUpgrade: websocket\r\n"
           b"Connection: Upgrade\r\nSec-WebSocket-Key: AAAAAAAAAAAAAAAAAAAAAA==\r\n"
           b"Sec-WebSocket-Version: 13\r\n\r\n")
    if dribble:
        for i in range(0, len(req), 7):
            s.sendall(req[i:i + 7]); time.sleep(0.0005)
    else:
        s.sendall(req)
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += s.recv(4096)
    assert b"101" in buf, buf

def mqtt_connect(cid):
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    return bytes([0x10, len(vh)]) + vh

def mqtt_publish(topic, payload, qos=0, pid=0):
    body = struct.pack(">H", len(topic)) + topic
    if qos:
        body += struct.pack(">H", pid)
    body += payload
    return bytes([0x30 | (qos << 1), len(body)]) + body

socks = [socket.create_connection(("127.0.0.1", wsp)) for _ in range(3)]
ids = []
framed = 0
deadline = time.time() + 15

def setup():
    for i, s in enumerate(socks):
        upgrade(s, dribble=(i == 0))
        s.sendall(frame(0x2, mqtt_connect(b"w%%d" %% i)))
su = threading.Thread(target=setup)
su.start()
while (len(ids) < 3 or framed < 3) and time.time() < deadline:
    for kind, conn, payload in host.poll(50):
        if kind == native.EV_OPEN:
            assert payload.startswith(b"ws:"), payload
            ids.append(conn)
        elif kind == native.EV_FRAME:
            framed += 1
            host.send(conn, b"\x20\x02\x00\x00")   # CONNACK (host wraps)
su.join()
assert len(ids) == 3 and framed == 3, (ids, framed)
sub, pub, extra = ids

for c in ids:
    host.enable_fast(c, 4, 64)
host.sub_add(sub, "w/+", 1, 0)
host.permit(pub, "w/x")

stop = threading.Event()
def control_churn():
    j = 0
    while not stop.is_set():
        host.sub_add(sub, "churn/%%d" %% (j %% 5), 0, 0)
        host.sub_del(sub, "churn/%%d" %% ((j + 2) %% 5))
        host.stats()
        for c in list(ids):
            host.send(c, b"\xd0\x00")              # cross-thread PINGRESP
        j += 1
        time.sleep(0.0003)
ctl = threading.Thread(target=control_churn)
ctl.start()

time.sleep(0.2)
N_MSG = 300
def blaster():
    for k in range(N_MSG):
        pkt = mqtt_publish(b"w/x", b"p%%03d" %% k, k & 1, 1 + (k %% 100))
        if k %% 5 == 0:
            # fragmented: binary FIN=0 + continuation FIN=1
            a, b = pkt[:4], pkt[4:]
            socks[1].sendall(frame(0x2, a, fin=False) + frame(0x0, b))
        elif k %% 7 == 0:
            socks[1].sendall(frame(0x9, b"hb"))     # ping mid-stream
            socks[1].sendall(frame(0x2, pkt))
        else:
            socks[1].sendall(frame(0x2, pkt))
        if k == N_MSG // 2:
            socks[2].sendall(frame(0x8, struct.pack(">H", 1000)))  # close
        time.sleep(0.0003)
bl = threading.Thread(target=blaster)
bl.start()

# subscriber acks native qos1 deliveries THROUGH the ws codec
def acker():
    buf = b""
    socks[0].settimeout(0.2)
    while not stop.is_set():
        try:
            chunk = socks[0].recv(8192)
        except (TimeoutError, OSError):
            continue
        if not chunk:
            return
        buf += chunk
        # minimal server-frame walk (unmasked, small payloads)
        while len(buf) >= 2:
            n = buf[1] & 0x7F
            off = 2
            if n == 126:
                if len(buf) < 4: break
                n = struct.unpack(">H", buf[2:4])[0]; off = 4
            if len(buf) < off + n: break
            payload, buf = buf[off:off + n], buf[off + n:]
            if payload and payload[0] >> 4 == 3 and (payload[0] >> 1) & 3 == 1:
                tlen = (payload[2] << 8) | payload[3]
                pid = (payload[4 + tlen] << 8) | payload[5 + tlen]
                try:
                    socks[0].sendall(frame(0x2, bytes([0x40, 2, pid >> 8, pid & 0xFF])))
                except OSError:
                    return
ack = threading.Thread(target=acker)
ack.start()

deadline = time.time() + 20
while time.time() < deadline:
    list(host.poll(20))
    st = host.stats()
    if (st["fast_in"] > N_MSG // 2 and st["ws_pings"] > 0
            and st["ws_closes"] > 0 and st["native_acks"] > 0):
        break
bl.join()
time.sleep(0.3)
stop.set(); ctl.join(); ack.join()
st = host.stats()
assert st["ws_handshakes"] == 3, st
assert st["fast_in"] > 0 and st["fast_out"] > 0, st
assert st["ws_pings"] > 0 and st["ws_closes"] > 0, st
assert st["native_acks"] > 0, st
# a rejected upgrade exercises the 400 path under the sanitizer too
bad = socket.create_connection(("127.0.0.1", wsp))
bad.settimeout(0.2)
bad.sendall(b"GET /other HTTP/1.1\r\nHost: x\r\nUpgrade: websocket\r\n"
            b"Connection: Upgrade\r\nSec-WebSocket-Key: A==\r\n\r\n")
for _ in range(20):
    list(host.poll(10))
    try:
        if b"400" in bad.recv(4096):
            break
    except (TimeoutError, OSError):
        pass
bad.close()
for s in socks:
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st)
"""


# Round-8 telemetry-plane coverage (ISSUE 3 satellite): histogram
# export + flight-recorder dumps under load, with set_trace /
# set_telemetry toggles racing the poll thread from a control thread,
# and a protocol-error teardown dumping the recorder mid-traffic.
DRIVER_TELEMETRY = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=4096)

def connect(cid):
    s = socket.create_connection(("127.0.0.1", host.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

def pub_frame(topic, payload, qos=0, pid=0):
    vh = struct.pack(">H", len(topic)) + topic
    if qos:
        vh += struct.pack(">H", pid)
    vh += payload
    return bytes([0x30 | (qos << 1), len(vh)]) + vh

socks = [connect(b"t%%02d" %% i) for i in range(6)]
conns = []
deadline = time.time() + 15
while len(conns) < 6 and time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == native.EV_OPEN:
            conns.append(conn)
assert len(conns) == 6, conns
pub_id, sub_id = conns[0], conns[1]
host.enable_fast(pub_id, 4)
host.sub_add(sub_id, "tele/t", qos=1)
host.permit(pub_id, "tele/t")
list(host.poll(20))

stop = threading.Event()
def toggler():
    # cross-thread control ops racing the poll thread (the contract
    # under test): trace punts flip and the telemetry master switch
    # cycles while publishes flow
    i = 0
    while not stop.is_set():
        host.set_trace(conns[2 + (i %% 4)], i %% 2 == 0)
        if i %% 7 == 0:
            host.set_telemetry(i %% 14 != 0, slow_ack_ms=0)
        i += 1
        time.sleep(0.002)
tog = threading.Thread(target=toggler)
tog.start()

tele_records = 0
flights = 0
hist_deltas = 0
for burst in range(30):
    for i in range(20):
        socks[0].sendall(pub_frame(b"tele/t", b"p%%02d" %% i,
                                   qos=(i %% 2), pid=100 + i))
    t0 = time.time()
    while time.time() - t0 < 0.05:
        for kind, conn, payload in host.poll(5):
            if kind == native.EV_TELEMETRY:
                tele_records += 1
                for rec in native.parse_telemetry(payload):
                    if rec[0] == "flight":
                        flights += 1
                    elif rec[0] == "hist":
                        hist_deltas += 1
stop.set(); tog.join()
host.set_telemetry(True, slow_ack_ms=0)
list(host.poll(20))
# protocol error mid-traffic: oversized remaining length tears down the
# conn and dumps its recorder
socks[0].sendall(bytes([0x30, 0xFF, 0xFF, 0xFF, 0x7F]))
deadline = time.time() + 5
closed = False
while not closed and time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == native.EV_CLOSED and conn == pub_id:
            closed = True
        elif kind == native.EV_TELEMETRY:
            for rec in native.parse_telemetry(payload):
                if rec[0] == "flight":
                    flights += 1
assert closed
assert tele_records > 0 and hist_deltas > 0, (tele_records, hist_deltas)
assert flights > 0, flights
st = host.stats()
assert st["telemetry_batches"] > 0 and st["fr_dumps"] > 0, st
for s in socks:
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st["telemetry_batches"], st["fr_dumps"])
"""


# Round-9 cluster-trunk coverage (ISSUE 4): TWO hosts in one process,
# each with its own poll thread, forwarding publishes over a loopback
# trunk link while a control thread races trunk connect/disconnect and
# route add/del ops against both poll threads — the first time two
# native hosts talk to each other, under both sanitizers.
DRIVER_TRUNK = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

A = native.NativeHost(port=0, max_size=1 << 16)
B = native.NativeHost(port=0, max_size=1 << 16)
tp = B.trunk_listen()

def connect(host, cid):
    s = socket.create_connection(("127.0.0.1", host.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

def pub_frame(topic, payload, qos=0, pid=0):
    vh = struct.pack(">H", len(topic)) + topic
    if qos:
        vh += struct.pack(">H", pid)
    vh += payload
    return bytes([0x30 | (qos << 1), len(vh)]) + vh

pub_s = connect(A, b"tp")
sub_s = connect(B, b"ts")
ids = {"A": [], "B": []}
framed = {"A": 0, "B": 0}
deadline = time.time() + 15
while ((not ids["A"] or not ids["B"] or framed["A"] < 1 or framed["B"] < 1)
       and time.time() < deadline):
    for name, h in (("A", A), ("B", B)):
        for kind, conn, payload in h.poll(20):
            if kind == native.EV_OPEN:
                ids[name].append(conn)
            elif kind == native.EV_FRAME:
                framed[name] += 1
                h.send(conn, b"\x20\x02\x00\x00")
assert ids["A"] and ids["B"], ids
pa, sb = ids["A"][0], ids["B"][0]
A.enable_fast(pa, 4)
A.permit(pa, "tr/x")
A.trunk_route_add(1, "tr/x")
A.trunk_connect(1, "127.0.0.1", tp)
B.enable_fast(sb, 4)
B.sub_add(sb, "tr/+", qos=1)

stop = threading.Event()
events = {"up": 0, "down": 0}
def poller(h):
    while not stop.is_set():
        for kind, conn, payload in h.poll(20):
            if kind == native.EV_TRUNK and payload:
                if payload[0] == native.TRUNK_UP:
                    events["up"] += 1
                elif payload[0] == native.TRUNK_DOWN:
                    events["down"] += 1
tA = threading.Thread(target=poller, args=(A,))
tB = threading.Thread(target=poller, args=(B,))
tA.start(); tB.start()

def churn():
    j = 0
    while not stop.is_set():
        A.trunk_route_add(1, "churn/%%d" %% (j %% 5))
        A.trunk_route_del(1, "churn/%%d" %% ((j + 2) %% 5))
        A.stats(); B.stats()
        if j %% 60 == 29:
            # teardown/reconnect racing the poll threads (keep state:
            # the replay ring survives and replays on the reconnect)
            A.trunk_disconnect(1, forget=False)
            A.trunk_connect(1, "127.0.0.1", tp)
        j += 1
        time.sleep(0.0005)
ctl = threading.Thread(target=churn)
ctl.start()

def drain():
    sub_s.settimeout(0.2)
    buf = b""
    while not stop.is_set():
        try:
            chunk = sub_s.recv(8192)
        except (TimeoutError, OSError):
            continue
        if not chunk:
            return
        buf += chunk
        # ack any qos1 deliveries so B's ack plane cycles too
        while len(buf) >= 2:
            ln = buf[1]
            if ln & 0x80 or len(buf) < 2 + ln:
                break
            frame, buf = buf[: 2 + ln], buf[2 + ln:]
            if frame[0] >> 4 == 3 and (frame[0] >> 1) & 3 == 1:
                tlen = (frame[2] << 8) | frame[3]
                pid = (frame[4 + tlen] << 8) | frame[5 + tlen]
                try:
                    sub_s.sendall(bytes([0x40, 2, pid >> 8, pid & 0xFF]))
                except OSError:
                    return
dr = threading.Thread(target=drain)
dr.start()

time.sleep(0.3)
N_MSG = 600
for k in range(N_MSG):
    pub_s.sendall(pub_frame(b"tr/x", b"p%%04d" %% k, k & 1,
                            1 + (k %% 100)))
    time.sleep(0.0004)

deadline = time.time() + 20
while time.time() < deadline:
    a, b = A.stats(), B.stats()
    if (a["trunk_out"] > N_MSG // 4 and b["trunk_in"] > 0
            and a["trunk_batches_out"] > 0 and events["up"] > 0):
        break
    time.sleep(0.05)
time.sleep(0.3)
stop.set()
ctl.join(); dr.join(); tA.join(); tB.join()
a, b = A.stats(), B.stats()
assert a["trunk_out"] > 0 and a["trunk_batches_out"] > 0, a
assert b["trunk_in"] > 0 and b["trunk_batches_in"] > 0, b
assert events["up"] > 0, events
for s in (pub_s, sub_s):
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(A.poll(10)); list(B.poll(10))
A.destroy(); B.destroy()
print("SANITIZED-RUN-OK", a["trunk_out"], b["trunk_in"], events)
"""


# Round-10 durable-plane coverage: the poll thread appends batched
# store records (FlushDurables) while foreign threads hammer the SAME
# DurableStore with fetch/consume/gc/stats (the resume-replay and
# marker-consumption call shapes) and race durable route add/del plus
# disable/enable_fast churn (kind-11 handoff emission) against it —
# the store's one-mutex contract under both sanitizers.
DRIVER_DURABLE = r"""
import socket, struct, sys, tempfile, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

store = native.NativeStore(tempfile.mkdtemp(), segment_bytes=1 << 16,
                           fsync="batch")
tok = store.register("dur-sess")
host = native.NativeHost(port=0, max_size=1 << 16)
host.attach_store(store)

def mqtt_connect(cid):
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    return bytes([0x10, len(vh)]) + vh

def mqtt_publish(topic, payload, qos=0, pid=0):
    body = struct.pack(">H", len(topic)) + topic
    if qos:
        body += struct.pack(">H", pid)
    body += payload
    return bytes([0x30 | (qos << 1), len(body)]) + body

socks = [socket.create_connection(("127.0.0.1", host.port))
         for _ in range(2)]
ids = []
for i, s in enumerate(socks):
    s.sendall(mqtt_connect(b"d%%d" %% i))
deadline = time.time() + 15
framed = 0
while (len(ids) < 2 or framed < 2) and time.time() < deadline:
    for kind, conn, payload in host.poll(50):
        if kind == native.EV_OPEN:
            ids.append(conn)
        elif kind == native.EV_FRAME:
            framed += 1
            host.send(conn, b"\x20\x02\x00\x00")
assert len(ids) == 2 and framed == 2, (ids, framed)
sub, pub = ids
for c in ids:
    host.enable_fast(c, 4, 64)
host.sub_add(sub, "du/x", qos=0)
host.durable_add(tok, "du/+", 1)
host.permit(pub, "du/x")

stop = threading.Event()
def store_churn():
    # the resume-replay / consume-on-ack shapes racing the poll
    # thread's batched appends on the store's internal mutex
    j = 0
    while not stop.is_set():
        rows = store.fetch(tok)
        if rows and j %% 3 == 0:
            store.consume(tok, [r[0] for r in rows[: len(rows) // 2 + 1]])
        store.pending(tok)
        store.stats()
        if j %% 40 == 17:
            store.gc()
        j += 1
        time.sleep(0.0005)

def meta_churn():
    # round 18: session-catalog writes, REGISTER retirement, and the
    # trunk-ring journal/ack/fetch surfaces racing the poll thread's
    # FlushDurables + FlushTrunkPeer appends on the same mutex
    j = 0
    while not stop.is_set():
        store.put_session("dur-gc", b'{"subs": {"g/%%d": {}}}' %% j)
        if j %% 7 == 3:
            store.unregister("dur-gc")
        store.sessions()
        store.trunk_put("peerZ", j + 1, b"R" * 48, has_trace=(j & 1) == 1)
        store.trunk_fetch("peerZ")
        if j %% 2:
            store.trunk_ack("peerZ", j + 1)
        store.trunk_pending("peerZ")
        j += 1
        time.sleep(0.0007)

def control_churn():
    # durable route flips + plane demote/promote (handoff emission) +
    # clientid rebinds (conn_cids_) + trunk-ident ring loads
    j = 0
    while not stop.is_set():
        if j %% 10 == 3:
            host.durable_del(tok, "du/+")
            host.durable_add(tok, "du/+", 1)
        if j %% 25 == 7:
            host.disable_fast(pub)
            host.enable_fast(pub, 4, 64, "d1")
            host.permit(pub, "du/x")
        if j %% 33 == 11:
            host.trunk_ident(9, "peerY")
        host.stats()
        j += 1
        time.sleep(0.0008)

th = [threading.Thread(target=store_churn),
      threading.Thread(target=meta_churn),
      threading.Thread(target=control_churn)]
for t in th: t.start()

N_MSG = 400
def blaster():
    for k in range(N_MSG):
        socks[1].sendall(mqtt_publish(b"du/x", b"p%%03d" %% k, k & 1,
                                      1 + (k %% 100)))
        time.sleep(0.0003)
bl = threading.Thread(target=blaster)
bl.start()

durable_events = 0
deadline = time.time() + 25
while time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == native.EV_DURABLE:
            base, ts, entries = native.parse_durable(payload)
            durable_events += len(entries)
    st = host.stats()
    if (st["durable_in"] > N_MSG // 4 and st["handoffs"] > 0
            and st["store_appends"] > 0):
        break
bl.join()
time.sleep(0.3)
stop.set()
for t in th: t.join()
st = host.stats()
assert st["durable_in"] > 0 and st["store_appends"] > 0, st
assert st["handoffs"] > 0, st
assert durable_events > 0, "no kind-10 records surfaced"
ss = store.stats()
assert ss["appends"] > 0, ss
for s in socks:
    try: s.close()
    except OSError: pass
for _ in range(10):
    list(host.poll(10))
host.destroy()
store.close()
print("SANITIZED-RUN-OK", st["durable_in"], st["handoffs"], ss["appends"])
"""


DRIVER_SN = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)
sn_port = host.listen_sn("127.0.0.1", 0, gw_id=3)
host.sn_predefined(1, "pre/one")

def sn_connect(cid, clean=True, duration=60):
    body = bytes([0x04, 0x04 if clean else 0x00, 0x01]) + \
        struct.pack(">H", duration) + cid
    return bytes([len(body) + 1]) + body

def sn_subscribe(mid, topic):
    body = bytes([0x12, 0x00]) + struct.pack(">H", mid) + topic
    return bytes([len(body) + 1]) + body

def sn_publish_predef(tid, data, qos=0, mid=0):
    fl = (0x60 if qos == -1 else qos << 5) | 0x01
    body = bytes([0x0C, fl]) + struct.pack(">HH", tid, mid) + data
    return bytes([len(body) + 1]) + body

def sn_register(mid, topic):
    body = bytes([0x0A]) + struct.pack(">HH", 0, mid) + topic
    return bytes([len(body) + 1]) + body

def sn_short(name2, data):
    tid = (name2[0] << 8) | name2[1]
    body = bytes([0x0C, 0x02]) + struct.pack(">HH", tid, 0) + data
    return bytes([len(body) + 1]) + body

PING = bytes([2, 0x16])
DISC = bytes([2, 0x18])

stop = threading.Event()

def retain_churn():
    # retained-snapshot swaps (set/del/expiry-free) + predefined-id
    # flips racing the poll thread's SUBSCRIBE-triggered matching
    j = 0
    while not stop.is_set():
        host.set_retained("r/%%d" %% (j %% 24), b"v%%05d" %% j, j & 1, 0)
        if j %% 5 == 3:
            host.retain_del("r/%%d" %% ((j + 7) %% 24))
        if j %% 17 == 11:
            host.sn_predefined(1, None)
            host.sn_predefined(1, "pre/one")
        host.stats()
        j += 1
        time.sleep(0.0004)

def udp_churn(seed):
    # datagram conn churn: connect (identities recycle so the addr
    # slot sees successor re-CONNECTs), register, subscribe (fires
    # retained delivery), publish qos0/1 via predefined + short ids,
    # ping, sometimes vanish without DISCONNECT
    j = 0
    while not stop.is_set():
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.settimeout(0.05)
        s.connect(("127.0.0.1", sn_port))
        s.send(sn_connect(b"churn-%%d-%%d" %% (seed, j %% 3)))
        s.send(sn_register(1 + (j & 0xFF), b"reg/%%d" %% (j %% 8)))
        s.send(sn_subscribe(2 + (j & 0xFF), b"r/+"))
        s.send(sn_publish_predef(1, b"p%%04d" %% j, qos=j %% 2,
                                 mid=10 + (j & 0xFF)))
        s.send(sn_short(b"ab", b"s%%d" %% j))
        s.send(PING)
        try:
            while True:
                s.recv(4096)
        except OSError:
            pass
        if j %% 3 != 0:
            s.send(DISC)
        s.close()
        j += 1

def qosm1_blaster():
    # publish-without-connect: every datagram rides the shared
    # anonymous conn
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.connect(("127.0.0.1", sn_port))
    j = 0
    while not stop.is_set():
        s.send(sn_publish_predef(1, b"m1-%%04d" %% j, qos=-1))
        j += 1
        time.sleep(0.0005)
    s.close()

th = [threading.Thread(target=retain_churn),
      threading.Thread(target=udp_churn, args=(1,)),
      threading.Thread(target=udp_churn, args=(2,)),
      threading.Thread(target=qosm1_blaster)]
for t in th: t.start()

# main thread plays the Python plane exactly like native_server: answer
# CONNECT/SUBSCRIBE punts, fast-enable + permit, fire the retained seam
deadline = time.time() + 25
while time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind != native.EV_FRAME:
            continue
        t = payload[0] >> 4
        if t == 1:                                  # CONNECT
            host.send(conn, b"\x20\x02\x00\x00")
            host.enable_fast(conn, 4, 32)
            host.permit(conn, "pre/one")
        elif t == 8:                                # SUBSCRIBE
            pid = struct.unpack(">H", payload[2:4])[0]
            tl = struct.unpack(">H", payload[4:6])[0]
            filt = payload[6:6 + tl].decode()
            host.sub_add(conn, filt, qos=0)
            host.send(conn, b"\x90\x03" + struct.pack(">H", pid) + b"\x00")
            host.retain_deliver(conn, filt, 1)
        elif t == 3:                                # punted PUBLISH
            qos = (payload[0] >> 1) & 3
            if qos:
                tl = struct.unpack(">H", payload[2:4])[0]
                pid = struct.unpack(">H", payload[4 + tl:6 + tl])[0]
                host.send(conn, b"\x40\x02" + struct.pack(">H", pid))
    st = host.stats()
    if (st["sn_in"] > 150 and st["retain_set"] > 150
            and st["retain_msgs_out"] > 20 and st["sn_qos_m1"] > 50):
        break

stop.set()
for t in th: t.join()
st = host.stats()
assert st["sn_in"] > 0 and st["sn_registers"] > 0, st
assert st["retain_set"] > 0 and st["retain_msgs_out"] > 0, st
assert st["sn_qos_m1"] > 0, st
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st["sn_in"], st["retain_msgs_out"])
"""


DRIVER_SHARDS = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

# Three shard hosts in one ring group (ISSUE 7): cross-shard publishes
# race set_trace/set_telemetry toggles across ALL poll threads, then
# shard 2 TEARS DOWN mid-traffic — the group-owned doorbells and the
# alive flag are what keep the racing producer memory-safe; afterwards
# the ladder degrades its deliveries ring-full/dead -> punt.
group = native.NativeShardGroup(3)
hosts = [native.NativeHost(port=0, max_size=1 << 16) for _ in range(3)]
for i, h in enumerate(hosts):
    h.join_group(group, i)

def connect(h, cid):
    s = socket.create_connection(("127.0.0.1", h.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

def pub_frame(topic, payload):
    vh = struct.pack(">H", len(topic)) + topic + payload
    return bytes([0x30, len(vh)]) + vh

pub_s = connect(hosts[0], b"sp")
sub1_s = connect(hosts[1], b"s1")
sub2_s = connect(hosts[2], b"s2")

ids = [[], [], []]
framed = [0, 0, 0]
deadline = time.time() + 15
while ((any(not i for i in ids) or any(f < 1 for f in framed))
       and time.time() < deadline):
    for k in range(3):
        for kind, conn, payload in hosts[k].poll(20):
            if kind == native.EV_OPEN:
                ids[k].append(conn)
            elif kind == native.EV_FRAME:
                framed[k] += 1
                hosts[k].send(conn, b"\x20\x02\x00\x00")
assert all(ids), ids
pub_id, sub1, sub2 = ids[0][0], ids[1][0], ids[2][0]
assert [native.shard_of(c) for c in (pub_id, sub1, sub2)] == [0, 1, 2]
hosts[0].enable_fast(pub_id, 4)
hosts[0].permit(pub_id, "sh/t")
hosts[1].enable_fast(sub1, 4)
hosts[2].enable_fast(sub2, 4)
for h in hosts:                     # the broadcast table discipline
    h.sub_add(sub1, "sh/t", 0, 0)
    h.sub_add(sub2, "sh/t", 0, 0)

stop = threading.Event()
stop2 = threading.Event()           # shard 2 stops early (teardown race)
def poller(k, ev):
    h = hosts[k]
    while not ev.is_set():
        list(h.poll(20))
threads = [threading.Thread(target=poller, args=(k, stop2 if k == 2 else stop))
           for k in range(3)]
for t in threads:
    t.start()

def blaster():
    f = pub_frame(b"sh/t", b"x" * 32) * 16
    while not stop.is_set():
        try:
            pub_s.sendall(f)
        except OSError:
            break
        time.sleep(0.001)
bt = threading.Thread(target=blaster)
bt.start()

def toggler():
    # trace punts + telemetry master switch flipped from a management
    # thread while every shard's poll thread is hot (hosts[2] is left
    # alone: its teardown below must not race a control call)
    j = 0
    while not stop.is_set():
        hosts[0].set_trace(pub_id, j %% 2 == 0)
        hosts[1].set_telemetry(j %% 3 != 0)
        hosts[0].stats(); hosts[1].stats()
        j += 1
        time.sleep(0.001)
tg = threading.Thread(target=toggler)
tg.start()

time.sleep(2.0)
# teardown race: shard 2 dies while shard 0 keeps shipping to it
stop2.set()
threads[2].join()
hosts[2].destroy()
time.sleep(1.0)
st = hosts[0].stats()
assert st["shard_ring_out"] > 0, st
stop.set()
bt.join(); tg.join()
for t in threads[:2]:
    t.join()
st0 = hosts[0].stats()
for s in (pub_s, sub1_s, sub2_s):
    s.close()
hosts[0].destroy(); hosts[1].destroy()
group.destroy()
print("SANITIZED-RUN-OK", st0["shard_ring_out"], st0["shard_ring_full"])
"""


DRIVER_TRACING = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

# Native distributed tracing (ISSUE 8): set_tracing toggles (enable,
# shift, seed) race SHARDED CROSS-NODE traffic — two shard hosts in a
# ring group blasting cross-shard deliveries while peer 1's OWNER shard
# (1 %% 2 = 1, the round-15 link spread) trunks a remote leg to a third
# (unsharded) host, kind-12 span batches flowing the whole time;
# set_trunk_wire flips race the HELLO negotiation.
group = native.NativeShardGroup(2)
hosts = [native.NativeHost(port=0, max_size=1 << 16) for _ in range(2)]
for i, h in enumerate(hosts):
    h.join_group(group, i)
peer = native.NativeHost(port=0, max_size=1 << 16)
peer_trunk = peer.trunk_listen()

def connect(h, cid):
    s = socket.create_connection(("127.0.0.1", h.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

def pub_frame(topic, payload):
    vh = struct.pack(">H", len(topic)) + topic + payload
    return bytes([0x30, len(vh)]) + vh

pub_s = connect(hosts[0], b"tp")
sub1_s = connect(hosts[1], b"t1")
subp_s = connect(peer, b"tb")

ids = [[], [], []]
all_hosts = hosts + [peer]
deadline = time.time() + 15
while any(not i for i in ids) and time.time() < deadline:
    for k, h in enumerate(all_hosts):
        for kind, conn, payload in h.poll(20):
            if kind == native.EV_OPEN:
                ids[k].append(conn)
            elif kind == native.EV_FRAME:
                h.send(conn, b"\x20\x02\x00\x00")
assert all(ids), ids
pub_id, sub1, subp = ids[0][0], ids[1][0], ids[2][0]
hosts[0].enable_fast(pub_id, 4)
hosts[0].permit(pub_id, "tr/t")
hosts[1].enable_fast(sub1, 4)
peer.enable_fast(subp, 4)
for h in hosts:                     # broadcast table + remote route
    h.sub_add(sub1, "tr/t", 0, 0)
    h.trunk_route_add(1, "tr/t")
peer.sub_add(subp, "tr/t", 0, 0)
# round 15: peer 1's link lives on its OWNER shard (1 %% 2 = 1); the
# publisher on shard 0 ring-forwards the trunk leg there
hosts[1].trunk_connect(1, "127.0.0.1", peer_trunk)

stop = threading.Event()
def poller(h):
    while not stop.is_set():
        for kind, conn, payload in h.poll(20):
            if kind == native.EV_SPANS:
                native.parse_spans(payload)   # decode under race too
threads = [threading.Thread(target=poller, args=(h,)) for h in all_hosts]
for t in threads:
    t.start()
time.sleep(0.5)
hosts[0].trunk_peer_state(1, True)  # the NON-owner shard's UP mirror

def blaster():
    f = pub_frame(b"tr/t", b"x" * 32) * 16
    while not stop.is_set():
        try:
            pub_s.sendall(f)
        except OSError:
            break
        time.sleep(0.001)
bt = threading.Thread(target=blaster)
bt.start()

def toggler():
    # the tracing control plane flipped from a management thread while
    # every poll thread is hot: enable/shift/seed churn plus trunk wire
    # caps racing the HELLO negotiation on redials
    j = 0
    while not stop.is_set():
        hosts[0].set_tracing(j %% 2 == 0, j %% 7, (1 << 63) | (j << 44))
        hosts[1].set_tracing(j %% 3 != 0, 0, (1 << 62) | (j << 44))
        peer.set_tracing(True, 0, 1 << 61)
        if j %% 5 == 0:
            peer.set_trunk_wire(j %% 2)
        hosts[0].stats(); peer.stats()
        j += 1
        time.sleep(0.001)
tg = threading.Thread(target=toggler)
tg.start()

time.sleep(3.0)
stop.set()
bt.join(); tg.join()
for t in threads:
    t.join()
st0 = hosts[0].stats()
stp = peer.stats()
for s in (pub_s, sub1_s, subp_s):
    s.close()
hosts[0].destroy(); hosts[1].destroy()
group.destroy()
peer.destroy()
assert st0["fast_in"] > 0, st0
assert st0["traced_pubs"] > 0, st0
assert st0["span_batches"] > 0, st0
print("SANITIZED-RUN-OK", st0["traced_pubs"], st0["span_batches"],
      stp["trunk_in"])
"""


# Round-15 faultline coverage: fault arm/disarm churn across EVERY site
# racing the poll threads (arming is all-atomics and explicitly allowed
# from any thread mid-traffic), against a trunk pair + a durable store,
# with blackhole/errno/short modes cycling while qos0/1 traffic flows
# and the sites keep counting — the injector's threading contract under
# both sanitizers.
DRIVER_FAULT = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

A = native.NativeHost(port=0, max_size=1 << 16)
B = native.NativeHost(port=0, max_size=1 << 16)
store = native.NativeStore("", 1 << 20, "batch")
A.attach_store(store)
tp = B.trunk_listen()
A.set_trunk_ack_timeout(300)

def connect(host, cid):
    s = socket.create_connection(("127.0.0.1", host.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

def pub_frame(topic, payload, qos=0, pid=0):
    vh = struct.pack(">H", len(topic)) + topic
    if qos:
        vh += struct.pack(">H", pid)
    vh += payload
    return bytes([0x30 | (qos << 1), len(vh)]) + vh

pub_s = connect(A, b"fp")
sub_s = connect(A, b"fs")
ids, framed = [], 0
deadline = time.time() + 15
while (len(ids) < 2 or framed < 2) and time.time() < deadline:
    for kind, conn, payload in A.poll(20):
        if kind == native.EV_OPEN:
            ids.append(conn)
        elif kind == native.EV_FRAME:
            framed += 1
            A.send(conn, b"\x20\x02\x00\x00")
    list(B.poll(0))
assert len(ids) == 2, ids
pub, sub = ids
A.enable_fast(pub, 4)
A.enable_fast(sub, 4)
A.sub_add(sub, "fl/+", qos=1)
A.permit(pub, "fl/x")
A.trunk_route_add(1, "fl/x")
A.trunk_connect(1, "127.0.0.1", tp)

stop = threading.Event()
def poller(h):
    while not stop.is_set():
        list(h.poll(20))
tB = threading.Thread(target=poller, args=(B,))
tB.start()

SITES = list(native.FAULT_SITES)
MODES = ["errno", "short", "blackhole", "full", "skew", "off"]
def churner(salt):
    j = 0
    while not stop.is_set():
        site = SITES[(j + salt) %% len(SITES)]
        mode = MODES[j %% len(MODES)]
        try:
            A.fault_arm(site, mode, n_or_prob=(j %% 3) * 0.25,
                        seed=j + 1, key=0)
        except ValueError:
            pass
        A.fault_fired(site)
        store.fault_arm("store_msync", MODES[(j + 1) %% len(MODES)],
                        n_or_prob=2, seed=j)
        if j %% 7 == 0:
            for s in SITES:
                A.fault_disarm(s)
        j += 1
        time.sleep(0.0005)
c1 = threading.Thread(target=churner, args=(0,))
c2 = threading.Thread(target=churner, args=(5,))
c1.start(); c2.start()

tok = store.register("fl-sid")
def store_hammer():
    k = 0
    while not stop.is_set():
        store.append(1, 1, [tok], "fl/d", b"s%%04d" %% k)
        if k %% 50 == 49:
            store.gc()
        k += 1
        time.sleep(0.0005)
sh = threading.Thread(target=store_hammer)
sh.start()

N_MSG = 1200
sub_s.settimeout(0.01)
for k in range(N_MSG):
    try:
        pub_s.sendall(pub_frame(b"fl/x", b"p%%04d" %% k, k & 1,
                                1 + (k %% 100)))
    except OSError:
        break                      # injected conn fault killed the pub
    for kind, conn, payload in A.poll(0):
        pass
    try:
        while sub_s.recv(8192):
            pass
    except (TimeoutError, OSError):
        pass
    time.sleep(0.0004)

time.sleep(0.2)
stop.set()
c1.join(); c2.join(); sh.join(); tB.join()
for s in SITES:
    A.fault_disarm(s)
a = A.stats()
assert a["fast_in"] > 0 or a["punts"] > 0, a
for _ in range(10):
    list(A.poll(10)); list(B.poll(10))
A.destroy(); B.destroy()
store.close()
print("SANITIZED-RUN-OK", a["faults_injected"])
"""


# Round 16 conn-scale plane: park/inflate churn + connect/teardown
# storms racing the poll thread. One raw host with an aggressive park
# horizon; real socket conns connect, idle into hibernation, and wake
# (first byte / cross-thread send / delivery) while control threads
# churn set_park / set_keepalive / sub_add and a synthetic herd parks
# and re-inflates through deliveries — the wheel (keepalive + park
# timers), the parked-record slab, and the accept governor all run
# under ASan+TSan.
DRIVER_PARK = r"""
import socket, struct, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native

host = native.NativeHost(port=0, max_size=1 << 16)
host.set_park(True, park_after_ms=60, accept_burst=64)
host.synth_conns(2000, keepalive_ms=600000, sub_every=4,
                 topic_prefix="synth")

def connect(cid):
    s = socket.create_connection(("127.0.0.1", host.port))
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack(">H", len(cid)) + cid
    s.sendall(bytes([0x10, len(vh)]) + vh)
    return s

stop = threading.Event()
conns = []
lock = threading.Lock()

def churner(salt):
    # connect/teardown storm: half the conns idle long enough to park,
    # then either close or send (a parked first byte -> inflate)
    for round_ in range(8):
        if stop.is_set():
            return
        socks = []
        try:
            socks = [connect(b"pk%%d-%%d" %% (salt, round_ * 8 + i))
                     for i in range(8)]
        except OSError:
            pass
        time.sleep(0.12)  # beyond the park horizon
        for j, s in enumerate(socks):
            try:
                if j %% 2:
                    s.sendall(b"\xc0\x00")   # parked ping fast path
                    time.sleep(0.005)
                s.close()
            except OSError:
                pass

def controller():
    # control ops racing the poll thread: keepalive re-arms, park
    # toggles, table churn, cross-thread sends to (parked) conns
    n = 0
    while not stop.is_set():
        n += 1
        with lock:
            targets = list(conns)[-16:]
        for c in targets:
            host.set_keepalive(c, 5000 + (n %% 7) * 1000)
            host.send(c, b"\xd0\x00")
        host.set_park(True, park_after_ms=60 + (n %% 3) * 20,
                      accept_burst=64)
        host.sub_add((n %% 8) + 1, "churn/%%d" %% (n %% 32), qos=1)
        host.sub_del((n %% 8) + 1, "churn/%%d" %% ((n + 16) %% 32))
        time.sleep(0.01)

threads = [threading.Thread(target=churner, args=(i,)) for i in range(3)]
threads.append(threading.Thread(target=controller))
for t in threads: t.start()

deadline = time.time() + 9
parked_seen = 0
while time.time() < deadline:
    for kind, cid, payload in host.poll(20):
        if kind == native.EV_OPEN:
            with lock:
                conns.append(cid)
            host.send(cid, b"\x20\x02\x00\x00")
            host.enable_fast(cid, 4)
    st = host.stats()
    parked_seen = max(parked_seen, st["conns_parked"])
stop.set()
for t in threads: t.join()
for _ in range(10):
    list(host.poll(10))
cc = host.conn_counts()
st = host.stats()
assert parked_seen > 0, "nothing ever parked"
assert st["conns_inflated"] > 0, "nothing ever inflated"
host.destroy()
print("SANITIZED-RUN-OK", parked_seen, st["conns_inflated"],
      st["parked_pings"])
"""


DRIVER_COAP = r"""
import socket, sys, threading, time
sys.path.insert(0, %(repo)r)
from emqx_tpu import native
from emqx_tpu.gateway import coap as C

host = native.NativeHost(port=0, max_size=1 << 16)
coap_port = host.listen_coap("127.0.0.1", 0)
host.set_coap_ack_timeout(50)
f = C.Frame()

def req(code, segs, mid, token=b"t", obs=None, queries=(), payload=b"",
        con=True, extra=()):
    opts = [(C.OPT_URI_PATH, s) for s in segs] + list(extra)
    if obs is not None:
        opts.append((C.OPT_OBSERVE, obs))
    for q in queries:
        opts.append((C.OPT_URI_QUERY, q))
    return f.serialize(C.CoapMessage(C.CON if con else C.NON, code, mid,
                                     token, opts, payload))

stop = threading.Event()

def control_churn():
    # retained-mirror swaps + the plain-GET completeness gate + the
    # CON backoff knob flipping, all racing the poll thread's dispatch
    j = 0
    while not stop.is_set():
        host.set_retained("cr/" + str(j %% 16), b"v" + str(j).encode(),
                          j & 1, 0)
        if j %% 7 == 3:
            host.retain_del("cr/" + str((j + 5) %% 16))
        if j %% 11 == 5:
            host.coap_retain_state(j %% 2 == 0)
        if j %% 13 == 7:
            host.set_coap_ack_timeout(50 + (j %% 3) * 25)
        host.stats()
        j += 1
        time.sleep(0.0004)

def udp_churn(seed):
    # endpoint churn: observe register (CON), NON + CON-qos1 publishes
    # (with one byte-identical dup for the MID-dedup window), CoAP
    # pings, a block-wise punt, plain GETs against the flipping
    # retained gate, CON-notify ACK/RST answers, a new-identity
    # re-register, and endpoints that vanish mid-rexmit
    j = 0
    while not stop.is_set():
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.settimeout(0.05)
        s.connect(("127.0.0.1", coap_port))
        cid = ("clientid=ch-" + str(seed) + "-" + str(j %% 3)).encode()
        t = str(j %% 16).encode()
        s.send(req(C.GET, [b"ps", b"cr", t], 1, token=b"ob", obs=b"",
                   queries=[cid, b"qos=1"]))
        s.send(req(C.POST, [b"ps", b"cr", t], 2, token=b"p1",
                   queries=[cid], payload=b"x", con=False))
        dup = req(C.POST, [b"ps", b"cr", t], 3, token=b"p2",
                  queries=[cid, b"qos=1"], payload=b"y")
        s.send(dup)
        s.send(dup)
        s.send(f.serialize(C.CoapMessage(C.CON, C.EMPTY, 9, b"")))
        s.send(req(C.POST, [b"ps", b"blk"], 4, token=b"bk",
                   queries=[cid], payload=b"c",
                   extra=[(C.OPT_BLOCK1, b"\x08")]))
        s.send(req(C.GET, [b"ps", b"cr", t], 5, token=b"rd",
                   queries=[cid]))
        try:
            while True:
                for m in f.parse(s.recv(4096), None)[0]:
                    if m.type == C.CON:
                        t2 = C.ACK if (j + m.mid) %% 3 else C.RST
                        s.send(f.serialize(
                            C.CoapMessage(t2, C.EMPTY, m.mid, b"")))
        except OSError:
            pass
        if j %% 2:
            s.send(req(C.POST, [b"ps", b"cr", b"0"], 6,
                       queries=[b"clientid=re-" + str(seed).encode()],
                       payload=b"z", con=False))
        s.close()
        j += 1

th = [threading.Thread(target=control_churn),
      threading.Thread(target=udp_churn, args=(1,)),
      threading.Thread(target=udp_churn, args=(2,))]
for t in th: t.start()

# main thread plays the Python plane exactly like native_server: answer
# CONNECT/SUBSCRIBE/UNSUBSCRIBE/qos1 punts, fast-enable + permit, and
# serve kind-13 oracle punts with a canned response
import struct
deadline = time.time() + 25
while time.time() < deadline:
    for kind, conn, payload in host.poll(20):
        if kind == 13:
            try:
                m = f.parse(payload, None)[0][0]
            except Exception:
                continue
            if m.type in (0, 1) and m.code:
                host.coap_send(conn, f.serialize(C.CoapMessage(
                    C.ACK if m.type == 0 else C.NON, C.NOT_FOUND,
                    m.mid, m.token)))
            continue
        if kind != native.EV_FRAME:
            continue
        t = payload[0] >> 4
        if t == 1:                                  # CONNECT
            host.send(conn, b"\x20\x02\x00\x00")
            host.enable_fast(conn, 4, 32)
            for k in range(16):
                host.permit(conn, "cr/" + str(k))
        elif t == 8:                                # SUBSCRIBE
            pid = struct.unpack(">H", payload[2:4])[0]
            tl = struct.unpack(">H", payload[4:6])[0]
            filt = payload[6:6 + tl].decode()
            host.sub_add(conn, filt, qos=1)
            host.send(conn, b"\x90\x03" + struct.pack(">H", pid) + b"\x01")
        elif t == 10:                               # UNSUBSCRIBE
            pid = struct.unpack(">H", payload[2:4])[0]
            tl = struct.unpack(">H", payload[4:6])[0]
            host.sub_del(conn, payload[6:6 + tl].decode())
            host.send(conn, b"\xB0\x02" + struct.pack(">H", pid))
        elif t == 3:                                # punted PUBLISH
            qos = (payload[0] >> 1) & 3
            if qos:
                tl = struct.unpack(">H", payload[2:4])[0]
                pid = struct.unpack(">H", payload[4 + tl:6 + tl])[0]
                host.send(conn, b"\x40\x02" + struct.pack(">H", pid))
    st = host.stats()
    if (st["coap_in"] > 150 and st["coap_notifies"] > 20
            and st["coap_punts"] > 10 and st["coap_pings"] > 20):
        break

stop.set()
for t in th: t.join()
st = host.stats()
assert st["coap_in"] > 0 and st["coap_notifies"] > 0, st
assert st["coap_punts"] > 0 and st["coap_pings"] > 0, st
assert st["coap_dedup_hits"] > 0, st
for _ in range(10):
    list(host.poll(10))
host.destroy()
print("SANITIZED-RUN-OK", st["coap_in"], st["coap_notifies"],
      st["coap_giveups"])
"""


@pytest.mark.parametrize("sanitizer", ["address", "thread"])
@pytest.mark.parametrize("driver", ["host", "fastpath", "lane", "ws",
                                    "telemetry", "trunk", "durable", "sn",
                                    "shards", "tracing", "fault", "park",
                                    "coap"])
def test_host_cc_sanitized(sanitizer, driver, tmp_path):
    if sanitizer not in _SAN_LIBS:
        pytest.skip(f"{sanitizer} sanitizer runtime not available")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "EMQX_NATIVE_SANITIZE": sanitizer,
        "LD_PRELOAD": _SAN_LIBS[sanitizer],
        "ASAN_OPTIONS": "halt_on_error=1:detect_leaks=0",
        # leak detection off: the PYTHON interpreter under LD_PRELOAD
        # reports its own arena allocs; host.cc still gets full
        # use-after-free/overflow/race coverage
        "TSAN_OPTIONS": "halt_on_error=1:report_signal_unsafe=0",
    }
    src = {"host": DRIVER, "fastpath": DRIVER_FASTPATH,
           "lane": DRIVER_LANE, "ws": DRIVER_WS,
           "telemetry": DRIVER_TELEMETRY, "trunk": DRIVER_TRUNK,
           "durable": DRIVER_DURABLE, "sn": DRIVER_SN,
           "shards": DRIVER_SHARDS, "tracing": DRIVER_TRACING,
           "fault": DRIVER_FAULT, "park": DRIVER_PARK,
           "coap": DRIVER_COAP}[driver]
    proc = subprocess.run(
        [sys.executable, "-c", src % {"repo": repo}],
        capture_output=True, text=True, env=env, timeout=180)
    assert "SANITIZED-RUN-OK" in proc.stdout, (
        f"rc={proc.returncode}\nstdout={proc.stdout[-2000:]}\n"
        f"stderr={proc.stderr[-4000:]}")
    for marker in ("ERROR: AddressSanitizer", "WARNING: ThreadSanitizer",
                   "ERROR: ThreadSanitizer"):
        assert marker not in proc.stderr, proc.stderr[-4000:]

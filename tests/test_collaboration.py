"""Collaboration channel: rooms, relay, heartbeat eviction, reconnect,
polling fallback (reference: browser/remoteCollaborationService.ts)."""

import time

import pytest

from senweaver_ide_tpu.services.collaboration import (ROOM_CODE_ALPHABET,
                                                      CollabCoordinator,
                                                      CollabSession)


@pytest.fixture()
def coord():
    c = CollabCoordinator(heartbeat_timeout_s=1.0)
    c.start()
    yield c
    c.stop()


def _session(coord, cid, **kw):
    host, port = coord.address
    s = CollabSession(host, port, cid, heartbeat_interval_s=0.2, **kw)
    s.connect()
    return s


def _wait(pred, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def test_room_code_shape(coord):
    s = _session(coord, "host")
    try:
        code = s.create_room()
        assert len(code) == 6 and all(ch in ROOM_CODE_ALPHABET
                                      for ch in code)
        assert code in coord.rooms
    finally:
        s.close()


def test_relay_between_host_and_follower(coord):
    host = _session(coord, "trainer")
    follower = _session(coord, "operator")
    try:
        code = host.create_room()
        peers = follower.join(code)
        assert set(peers) == {"trainer", "operator"}
        assert _wait(lambda: any(e.get("type") == "peer_joined"
                                 for e in host.events))

        host.send({"event": "train_progress", "step": 42})
        assert _wait(lambda: any(
            e.get("type") == "message"
            and e.get("payload", {}).get("step") == 42
            for e in follower.events))
        # direction 2: control message back to the trainer
        follower.send({"cmd": "checkpoint_now"})
        assert _wait(lambda: any(
            e.get("type") == "message"
            and e.get("payload", {}).get("cmd") == "checkpoint_now"
            for e in host.events))
    finally:
        host.close()
        follower.close()


def test_join_unknown_room_errors(coord):
    s = _session(coord, "x")
    try:
        with pytest.raises(RuntimeError, match="unknown room"):
            s.join("NOPE99")
    finally:
        s.close()


def test_leave_notifies_and_empties_room(coord):
    host = _session(coord, "h")
    peer = _session(coord, "p")
    try:
        code = host.create_room()
        peer.join(code)
        peer.leave()
        assert _wait(lambda: any(e.get("type") == "peer_left"
                                 and e.get("peer") == "p"
                                 for e in host.events))
        host.leave()
        assert _wait(lambda: code not in coord.rooms)
    finally:
        host.close()
        peer.close()


def test_heartbeat_keeps_alive_and_silence_evicts(coord):
    host = _session(coord, "h")          # heartbeats every 0.2 s
    try:
        code = host.create_room()
        # a participant that never heartbeats: join via polling one-shot
        mute = CollabSession(*coord.address, "mute",
                             heartbeat_interval_s=999)
        mute.polling = True
        mute.join(code)
        assert "mute" in coord.rooms[code].participants
        # heartbeat timeout (1 s) evicts the mute peer, host told why
        assert _wait(lambda: any(e.get("type") == "peer_left"
                                 and e.get("reason") == "heartbeat_timeout"
                                 for e in host.events), timeout=5)
        assert "mute" not in coord.rooms[code].participants
        # the heartbeating host is still a member
        assert "h" in coord.rooms[code].participants
    finally:
        host.close()


def test_evicted_peer_is_readmitted_with_push_channel(coord):
    host = _session(coord, "h")
    peer = _session(coord, "p", max_reconnects=5)
    try:
        code = host.create_room()
        peer.join(code)
        # force-evict the peer server-side (as the reaper would)
        coord.rooms[code].participants.pop("p")
        # peer keeps talking over its still-open connection → readmitted
        peer.send({"after": "eviction"})
        assert "p" in coord.rooms[code].participants
        assert _wait(lambda: any(e.get("reason") == "readmitted"
                                 for e in host.events))
        # and live push still reaches it (conn was re-attached)
        host.send({"hello": "again"})
        assert _wait(lambda: any(
            e.get("type") == "message"
            and e.get("payload", {}).get("hello") == "again"
            for e in peer.events))
    finally:
        host.close()
        peer.close()


def test_missing_room_field_is_not_unknown_room(coord):
    s = _session(coord, "x")
    try:
        with pytest.raises(RuntimeError, match="missing 'room'"):
            s._request({"op": "send", "payload": 1})
    finally:
        s.close()


def test_polling_fallback_drains_queue(coord):
    host = _session(coord, "h")
    poller = CollabSession(*coord.address, "poller")
    poller.polling = True               # degraded mode from the start
    try:
        code = host.create_room()
        poller.join(code)
        host.send({"n": 1})
        host.send({"n": 2})
        time.sleep(0.1)
        msgs = poller.poll()
        assert [m["payload"]["n"] for m in msgs
                if m.get("type") == "message"] == [1, 2]
        assert poller.poll() == []       # drained
    finally:
        host.close()


def test_reconnect_rejoins_room(coord):
    host = _session(coord, "h")
    peer = _session(coord, "p")
    try:
        code = host.create_room()
        peer.join(code)
        # sever the peer's transport out from under it
        with peer._conn_lock:
            peer._conn.close()
        # next send reconnects + rejoins, then relays successfully
        assert _wait(lambda: (peer.send({"back": True}) or True)
                     if not peer.polling else False, timeout=5)
        # budget restored after the successful reconnect; still live-push
        assert peer.reconnects_used == 0 and not peer.polling
        assert _wait(lambda: any(
            e.get("type") == "message"
            and e.get("payload", {}).get("back") for e in host.events))
    finally:
        host.close()
        peer.close()


def test_reconnect_exhaustion_falls_back_to_polling():
    coord = CollabCoordinator(heartbeat_timeout_s=30)
    coord.start()
    host, port = coord.address
    s = CollabSession(host, port, "p", heartbeat_interval_s=999,
                      max_reconnects=2)
    s.connect()
    try:
        h = CollabSession(host, port, "h", heartbeat_interval_s=0.2)
        h.connect()
        code = h.create_room()
        s.join(code)
        h.send({"n": 7})
        time.sleep(0.2)
        s.poll()                        # consume over the live conn
    finally:
        pass
    # coordinator goes away → reconnects exhaust → polling mode
    coord.stop()
    with s._conn_lock:
        dead = s._conn
        if dead is not None:    # None: the read loop saw the drop first
            dead.close()
    s._handle_disconnect(dead)  # then waits for it on the reconnect lock
    assert s.polling and s.reconnects_used == 2
    h.close()
    s.close()

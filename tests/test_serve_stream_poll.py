"""One poll a proxy and replica, not one a stream (ISSUE 42): a proxy takes the
chunks of ALL its streams on a replica in one ``next_stream_chunks`` call.

The first half drives a ``Replica`` in this process as a proxy would; the
second streams over HTTP through the real proxy to one replica whose chunks
the test lets out one by one (``Gated``). Both stretch the half second a poll
may wait to many seconds, so that a stream that has to wait out another's
poll fails its test instead of passing half a second late.
"""

import collections
import functools
import http.client
import json
import pickle
import socket
import threading
import time

import pytest

from ray_tpu.serve._private import replica as replica_mod

LONG_S = 20.0  # what a poll may wait here
SOON_S = 8.0  # what nothing that works takes

# ---------------------------------------------------------------------------
# a Replica in this process, the test as its proxies
# ---------------------------------------------------------------------------

GATES: dict = collections.defaultdict(lambda: threading.Semaphore(0))
BROKEN: set = set()


def _gated(request):
    """Streams ``<name><i>;``; each chunk waits for a permit."""
    from ray_tpu.serve.api import StreamingResponse

    name, n = request.query_params["name"], int(request.query_params["n"])

    def gen():
        for i in range(n):
            assert GATES[name].acquire(timeout=60)
            if name in BROKEN:
                raise ValueError(f"{name} broke")
            yield f"{name}{i};"

    return StreamingResponse(gen(), content_type="text/plain")


DELIVERED: dict = {}


def _stamped(request):
    """``_gated`` whose stamps come back, by stream."""
    from ray_tpu.serve.api import StreamingResponse

    name = request.query_params["name"]

    def gen():
        for i in range(2):
            assert GATES[name].acquire(timeout=60)
            yield f"{name}{i};"

    return StreamingResponse(gen(), content_type="text/plain", on_delivered=DELIVERED[name].extend)


@pytest.fixture
def rep(monkeypatch):
    monkeypatch.setattr(replica_mod, "_POLL_WAIT_S", LONG_S)
    GATES.clear()
    BROKEN.clear()
    return replica_mod.Replica(pickle.dumps((_gated, (), {})))


def _open(rep, name, n):
    return rep.handle_http_request("GET", "/", {"name": name, "n": str(n)}, b"", {})["__serve_stream__"]


def _let(name, k=1):
    for _ in range(k):
        GATES[name].release()


def _poll(rep, sids, poller="p", n=1):
    """The poll on a thread of its own: ``(thread, [reply])``."""
    out: list = []
    asked = [(sid, 0, ()) for sid in sids]
    t = threading.Thread(target=lambda: out.append(rep.next_stream_chunks((poller, n, time.monotonic_ns(), asked))))
    t.start()
    return t, out


def _until(rep, sid, want: int, **poll):
    """Polls the one stream until it has given ``want`` chunks."""
    chunks: list = []
    while len(chunks) < want:
        t, out = _poll(rep, [sid], **poll)
        t.join(SOON_S)
        assert out, "a poll whose stream had a chunk did not return"
        chunks += (out[0].get(sid) or {"chunks": []})["chunks"]
    return chunks


def test_a_poll_returns_as_soon_as_any_of_its_streams_has_a_chunk_with_that_stream_s_alone(rep):
    a, b, c = _open(rep, "a", 2), _open(rep, "b", 2), _open(rep, "c", 2)
    t, out = _poll(rep, [a, b, c])
    time.sleep(0.2)
    assert t.is_alive()  # nothing yet: it waits inside the replica
    _let("b")
    t.join(SOON_S)
    assert out == [{b: {"chunks": [b"b0;"], "done": False}}]  # and nothing about the streams that had nothing
    _let("a"), _let("c"), _let("c")
    got = {a: [], c: []}
    done = {}
    while len(done) < 1:  # what is ready leaves: no wait for the others
        t, out = _poll(rep, [a, b, c], n=2)
        t.join(SOON_S)
        for sid, batch in out[0].items():
            got[sid] += batch["chunks"]
            if batch["done"]:
                done[sid] = True
    assert got[c] == [b"c0;", b"c1;"] and done == {c: True} and got[a] in ([], [b"a0;"])
    assert sorted(rep._streams) == sorted([a, b])  # a stream's end took that stream alone


def test_one_stream_is_the_one_element_case_of_the_same_call(rep):
    a = _open(rep, "a", 3)
    _let("a", 3)
    chunks, done = [], False
    while not done:
        batch = rep.next_stream_chunk(a)
        assert set(batch) == {"chunks", "done"}
        chunks += batch["chunks"]
        done = batch["done"]
    assert chunks == [b"a0;", b"a1;", b"a2;"]
    assert rep.next_stream_chunk(a) is None and rep.next_stream_chunks(("p", 1, 0, [(a, 0, ())])) == {a: None}


def test_a_producer_s_error_reaches_its_stream_alone_after_the_chunks_before_it(rep):
    from ray_tpu.exceptions import TaskError

    a, b = _open(rep, "a", 3), _open(rep, "b", 3)
    _let("a"), _let("b")
    assert _until(rep, a, 1) == [b"a0;"] and _until(rep, b, 1) == [b"b0;"]
    BROKEN.add("a")
    _let("a"), _let("b")
    seen: dict = {}
    deadline = time.monotonic() + SOON_S
    while ("error" not in seen.get(a, {}) or not seen.get(b, {}).get("chunks")) and time.monotonic() < deadline:
        t, out = _poll(rep, [s for s in (a, b) if s in rep._streams])
        t.join(SOON_S)
        seen.update(out[0])
    assert seen[b] == {"chunks": [b"b1;"], "done": False}
    assert seen[a]["chunks"] == [] and isinstance(seen[a]["error"], TaskError)
    assert isinstance(seen[a]["error"].cause, ValueError) and "a broke" in seen[a]["error"].remote_traceback
    pickle.loads(pickle.dumps(seen[a]["error"]))  # it rides home in the reply
    assert list(rep._streams) == [b]
    _let("b")
    assert _until(rep, b, 1) == [b"b2;"]  # the other stream runs on


def test_the_one_stream_poll_raises_what_the_producer_raised(rep):
    a = _open(rep, "a", 2)
    BROKEN.add("a")
    _let("a")
    with pytest.raises(ValueError, match="a broke"):
        for _ in range(3):
            rep.next_stream_chunk(a)


def test_an_error_that_does_not_pickle_goes_as_its_text():
    class Odd(Exception):
        def __reduce__(self):
            raise TypeError("no")

    err = replica_mod._shippable(Odd("strange"))
    assert isinstance(err.cause, RuntimeError) and "strange" in str(err.cause)
    pickle.loads(pickle.dumps(err))


@pytest.mark.parametrize("first", ["the_poll", "the_wake"])
def test_a_wake_returns_the_poll_it_names_at_once_whichever_arrives_first(rep, first):
    a, b = _open(rep, "a", 1), _open(rep, "b", 1)
    if first == "the_wake":
        rep.wake_stream_poll(("p", 5))
    t, out = _poll(rep, [a], n=5)
    if first == "the_poll":
        time.sleep(0.2)
        assert t.is_alive()
        rep.wake_stream_poll(("p", 5))
    t.join(SOON_S)
    assert out == [{}]
    later, out = _poll(rep, [a], n=6)  # the wake was poll 5's: the next one waits again
    other, _ = _poll(rep, [b], poller="q", n=5)  # and it was poller p's
    time.sleep(0.2)
    assert later.is_alive() and other.is_alive()
    rep.wake_stream_poll(("p", 4))  # an older wake than the one on record changes nothing
    time.sleep(0.1)
    assert later.is_alive() and rep._poll_kicks == {"p": 5}
    _let("a"), _let("b")
    later.join(SOON_S), other.join(SOON_S)
    assert not later.is_alive() and not other.is_alive() and out[0][a]["chunks"] == [b"a0;"]


def test_two_proxies_polls_take_their_own_streams_chunks_only(rep):
    a, b = _open(rep, "a", 2), _open(rep, "b", 2)
    ta, outa = _poll(rep, [a], poller="p")
    tb, outb = _poll(rep, [b], poller="q")
    _let("b")
    tb.join(SOON_S)
    assert outb == [{b: {"chunks": [b"b0;"], "done": False}}]
    time.sleep(0.1)
    assert ta.is_alive() and not outa  # the other proxy's poll saw nothing of it
    _let("a", 2)
    ta.join(SOON_S)
    assert list(outa[0]) == [a] and outa[0][a]["chunks"][0] == b"a0;"


def test_the_idle_reaper_s_clock_is_kept_for_every_stream_a_poll_names(rep):
    a, b = _open(rep, "a", 1), _open(rep, "b", 2)
    for sid in (a, b):
        rep._streams[sid].last_pump -= 200.0
    before = time.time()
    _let("b")
    t, _ = _poll(rep, [a, b])
    t.join(SOON_S)
    assert rep._streams[a].last_pump >= before and rep._streams[b].last_pump >= before


def test_the_replica_counts_the_polls_that_carried_a_chunk_and_what_they_carried(rep):
    a, b = _open(rep, "a", 2), _open(rep, "b", 2)
    zero = {k: v for k, v in rep.get_metrics().items() if k.startswith("stream_poll")}
    assert zero == {"stream_polls": 0, "stream_poll_chunks": 0, "stream_poll_streams": 0}
    rep.wake_stream_poll(("p", 1))
    t, out = _poll(rep, [a, b], n=1)  # carries nothing: no count
    t.join(SOON_S)
    assert out == [{}] and rep.get_metrics()["stream_polls"] == 0
    _let("a", 2), _let("b", 2)
    deadline = time.monotonic() + SOON_S
    while not all(rep._streams[s].q.qsize() == 3 for s in (a, b)) and time.monotonic() < deadline:
        time.sleep(0.01)  # two chunks and the end, each
    t, out = _poll(rep, [a, b], n=2)
    t.join(SOON_S)
    assert {s: (len(bt["chunks"]), bt["done"]) for s, bt in out[0].items()} == {a: (2, True), b: (2, True)}
    m = rep.get_metrics()
    assert (m["stream_polls"], m["stream_poll_chunks"], m["stream_poll_streams"]) == (1, 4, 2)


def test_the_stamps_of_a_shared_poll():
    """One ``t_asked_ns`` and one ``t_enter_ns`` a poll, whatever streams it
    carried; the proxy's two last of a stream's batch with the next poll that
    names THAT stream, and never for its last batch."""
    GATES.clear()
    delivered = DELIVERED
    delivered.update(a=[], b=[])
    rep = replica_mod.Replica(pickle.dumps((_stamped, (), {})))
    a, b = _open(rep, "a", 2), _open(rep, "b", 2)
    _let("a"), _let("b")
    while not all(rep._streams[s].q.qsize() for s in (a, b)):
        time.sleep(0.01)
    reply = rep.next_stream_chunks(("p", 1, 111, [(a, 0, ()), (b, 0, ())]))
    assert sorted(reply) == sorted([a, b]) and delivered == {"a": [], "b": []}  # the proxy's stamps may still come
    _let("a")
    while rep._streams[a].q.qsize() < 2:
        time.sleep(0.01)
    now = time.monotonic_ns()
    reply = rep.next_stream_chunks(("p", 2, 222, [(a, now, [now + 1])]))  # b's client is slow: not named
    assert reply == {a: {"chunks": [b"a1;"], "done": True}}
    assert [s[1] for s in delivered["a"]] == [111, 222] and delivered["b"] == []
    first, last = delivered["a"]
    assert first[4:] == (now, now + 1) and last[4:] == (0, 0)  # a last batch has no next poll
    _let("b")
    while rep._streams[b].q.qsize() < 2:
        time.sleep(0.01)
    rep.next_stream_chunks(("p", 3, 333, [(b, now + 5, [now + 6])]))
    assert delivered["b"][0][1:3] == first[1:3] and delivered["b"][0][4:] == (now + 5, now + 6)  # poll 1's, both
    assert delivered["b"][1][1] == 333 and len({s[2] for s in delivered["a"] + delivered["b"]}) == 3  # three polls


# ---------------------------------------------------------------------------
# over HTTP: client -> proxy -> one replica
# ---------------------------------------------------------------------------


class Gated:
    """``GET /g?name=a&n=5`` streams ``a0;a1;...``: a chunk waits for a permit
    (``/g/ctl?grant=a&k=2``), or, with ``tick=`` and ``start=``, for its time
    on a schedule all such streams share; ``size=`` pads a chunk. ``/g/ctl``
    also breaks a stream (``fail=a``) and tells what the replica saw."""

    def __init__(self, poll_wait_s: float):
        from ray_tpu.serve._private import replica

        replica._POLL_WAIT_S = poll_wait_s
        self.lock = threading.Lock()
        self.gates: dict = {}
        self.broken: set = set()
        self.cancelled: list = []
        self.yielded: dict = collections.Counter()

    def gate(self, name):
        with self.lock:
            return self.gates.setdefault(name, threading.Semaphore(0))

    def __call__(self, request):
        from ray_tpu.serve.api import StreamingResponse

        q = request.query_params
        if request.path.endswith("/ctl"):
            for _ in range(int(q.get("k", 0))):
                self.gate(q["grant"]).release()
            if "fail" in q:
                self.broken.add(q["fail"])
                self.gate(q["fail"]).release()
            return {"cancelled": list(self.cancelled), "yielded": dict(self.yielded)}
        name, n, pad = q["name"], int(q["n"]), b"x" * int(q.get("size", 0))
        tick, start = float(q.get("tick", 0)), float(q.get("start", 0))
        gate = self.gate(name)

        def gen():
            for i in range(n):
                if tick:
                    time.sleep(max(0.0, start + i * tick - time.time()))
                elif "free" not in q:
                    assert gate.acquire(timeout=120)
                if name in self.broken:
                    raise RuntimeError(f"{name} broke")
                self.yielded[name] += 1
                yield f"{name}{i};".encode() + pad

        return StreamingResponse(
            gen(), content_type="text/plain", on_disconnect=lambda: self.cancelled.append(name)
        )


@pytest.fixture(scope="module")
def gated():
    """(the proxy's address, the replica's actor)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve._private.common import CONTROLLER_NAME

    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    try:
        serve.start()
        serve.run(serve.deployment(max_concurrent_queries=64)(Gated).bind(LONG_S), route_prefix="/g")
        table = ray_tpu.get(ray_tpu.get_actor(CONTROLLER_NAME).get_routing_table.remote(-2, 0.1))["table"]
        (replica,) = table["Gated"]["replicas"]
        yield serve.http_address(), ray_tpu.get_actor(replica["actor_name"])
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


class _Client:
    """One streamed GET, read when the test says so."""

    def __init__(self, addr, path, rcvbuf: int = 0):
        self.conn = http.client.HTTPConnection(*addr, timeout=SOON_S)
        if rcvbuf:
            self.conn.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self.conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
            self.conn.sock.settimeout(SOON_S)
            self.conn.sock.connect(addr)
        self.conn.request("GET", path)
        self.buf = b""

    @functools.cached_property
    def resp(self):
        """The proxy starts a response with its first chunk."""
        resp = self.conn.getresponse()
        assert resp.status == 200
        return resp

    def until(self, needle: bytes) -> float:
        """Reads until ``needle`` has come; the seconds it took."""
        t0 = time.monotonic()
        while needle not in self.buf:
            data = self.resp.read1(1 << 16)
            assert data, f"the stream ended before {needle!r}: {self.buf[-80:]!r}"
            self.buf += data
        return time.monotonic() - t0

    def rest(self) -> bytes:
        self.conn.sock.settimeout(60)
        self.buf += self.resp.read()
        self.conn.close()
        return self.buf


def _ctl(addr, **query):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("GET", "/g/ctl?" + "&".join(f"{k}={v}" for k, v in query.items()))
    out = json.loads(conn.getresponse().read())
    conn.close()
    return out


def _want(name, n, size=0):
    return b"".join(f"{name}{i};".encode() + b"x" * size for i in range(n))


def _together(addr, names, n, tick):
    """The bytes each of ``names`` read, all streaming at once on one schedule."""
    got: dict = {}
    start = time.time() + 0.5

    def read(name):
        got[name] = _Client(addr, f"/g?name={name}&n={n}&tick={tick}&start={start}").rest()

    threads = [threading.Thread(target=read, args=(name,)) for name in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    return got


def test_each_of_many_streams_gets_its_own_chunks_in_order_the_bytes_it_gets_alone(gated):
    addr, _ = gated
    alone = _Client(addr, "/g?name=m3&n=25&free=1").rest()
    assert alone == _want("m3", 25)
    got = _together(addr, [f"m{i}" for i in range(6)], 25, 0.01)
    assert got == {f"m{i}": _want(f"m{i}", 25) for i in range(6)} and got["m3"] == alone


def test_the_replica_answers_far_fewer_polls_than_it_hands_out_chunks(gated):
    import ray_tpu

    addr, replica = gated
    before = ray_tpu.get(replica.get_metrics.remote(), timeout=30)
    got = _together(addr, [f"p{i}" for i in range(8)], 30, 0.03)
    assert got == {f"p{i}": _want(f"p{i}", 30) for i in range(8)}
    after = ray_tpu.get(replica.get_metrics.remote(), timeout=30)
    polls, chunks, streams = (after[k] - before[k] for k in ("stream_polls", "stream_poll_chunks", "stream_poll_streams"))
    # a poll a stream and chunk would be 240 of each; a tick's eight chunks leave in a poll or two
    assert chunks == 240 and polls <= chunks // 2 and streams >= 2 * polls, (polls, chunks, streams)


def test_a_stream_opened_while_the_poll_waits_gets_its_first_chunk_at_once(gated):
    addr, _ = gated
    idle = _Client(addr, "/g?name=idle&n=1")  # its poll waits inside the replica, for LONG_S
    time.sleep(0.5)
    _ctl(addr, grant="new", k=1)
    new = _Client(addr, "/g?name=new&n=2")
    assert new.until(b"new0;") < SOON_S
    _ctl(addr, grant="new", k=1), _ctl(addr, grant="idle", k=1)
    assert new.rest() == _want("new", 2) and idle.rest() == _want("idle", 1)


def test_one_stream_s_error_and_another_s_end_leave_the_others_running(gated):
    addr, _ = gated
    a, b, c = (_Client(addr, f"/g?name=e{x}&n={n}") for x, n in (("a", 5), ("b", 3), ("c", 6)))
    for x in "abc":
        _ctl(addr, grant=f"e{x}", k=2)
    assert a.until(b"ea1;") < SOON_S and b.until(b"eb1;") < SOON_S and c.until(b"ec1;") < SOON_S
    _ctl(addr, fail="ea")
    assert a.rest() == _want("ea", 2)  # what it yielded before it raised, then the end
    _ctl(addr, grant="eb", k=1)
    assert b.rest() == _want("eb", 3)
    _ctl(addr, grant="ec", k=2)
    assert c.until(b"ec3;") < SOON_S
    _ctl(addr, grant="ec", k=2)
    assert c.rest() == _want("ec", 6)


def test_a_client_that_stops_reading_delays_nobody_and_its_backlog_stays_bounded(gated):
    addr, _ = gated
    n, size = 300, 1 << 16
    slow = _Client(addr, f"/g?name=slow&n={n}&size={size}&free=1", rcvbuf=4096)  # and does not read
    time.sleep(1.5)
    quick = _Client(addr, "/g?name=quick&n=4")
    _ctl(addr, grant="quick", k=2)
    assert quick.until(b"quick1;") < SOON_S
    held = _ctl(addr, grant="quick", k=2)["yielded"]["slow"]
    assert quick.rest() == _want("quick", 4)
    # the sockets' buffers, the replica's queue of 8 and one batch in the proxy: not the stream's 20 MB
    assert held < 200 and _ctl(addr)["yielded"]["slow"] < 200, held
    assert slow.rest() == _want("slow", n, size)  # and when it reads again, every byte in order


def test_a_disconnect_cancels_that_stream_alone(gated):
    addr, _ = gated
    gone, stays = _Client(addr, "/g?name=gone&n=50"), _Client(addr, "/g?name=stays&n=5")
    _ctl(addr, grant="gone", k=2), _ctl(addr, grant="stays", k=2)
    assert gone.until(b"gone1;") < SOON_S and stays.until(b"stays1;") < SOON_S
    gone.conn.close()
    deadline = time.monotonic() + SOON_S
    while "gone" not in _ctl(addr, grant="gone", k=1)["cancelled"] and time.monotonic() < deadline:
        time.sleep(0.05)  # the proxy learns of it as it writes
    _ctl(addr, grant="stays", k=3)
    assert stays.rest() == _want("stays", 5)
    assert _ctl(addr)["cancelled"].count("gone") == 1 and "stays" not in _ctl(addr)["cancelled"]


def test_two_proxies_on_one_replica_do_not_take_each_other_s_chunks(gated):
    import ray_tpu
    from ray_tpu.serve._private.common import CONTROLLER_NAME
    from ray_tpu.serve._private.http_proxy import HTTPProxy

    addr, replica = gated
    second = ray_tpu.remote(num_cpus=0, max_concurrency=16)(HTTPProxy).remote(CONTROLLER_NAME, "127.0.0.1", 0)
    try:
        addr2 = tuple(ray_tpu.get(second.address.remote(), timeout=60))
        assert addr2 != tuple(addr)
        got: dict = {}
        start = time.time() + 1.0

        def read(at, name):
            got[name] = _Client(at, f"/g?name={name}&n=20&tick=0.02&start={start}").rest()

        threads = [threading.Thread(target=read, args=((addr, addr2)[i % 2], f"t{i}")) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert got == {f"t{i}": _want(f"t{i}", 20) for i in range(6)}
    finally:
        ray_tpu.kill(second)

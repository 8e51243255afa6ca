"""A token's way back (ISSUE 38): the seven stamps from the scheduler's
``llm.emit`` to the proxy's write to the socket, the ``deliveries`` ring that
holds them, the collector's pauses beside it, and the two annotations of the
new host work, on the CPU backend with the tiny model of
``test_serve_llm_spans.py``.

One small cluster serves five concurrent streams over HTTP, once; the tests
of the chunk stamps themselves drive a ``Replica`` in this process, as the
proxy would. No test asserts a duration.
"""

import array
import gc
import glob
import json
import pickle
import threading
import time
import urllib.request

import pytest

from ray_tpu.serve._private import replica as replica_mod
from ray_tpu.serve.llm import stats

MODEL = dict(
    vocab_size=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=64, dtype="float32", remat=False,
)
ENGINE = dict(num_slots=3, block_size=4, max_model_len=32, prefill_chunk=4)
D = {name: i for i, name in enumerate(stats.DELIVERY_FIELDS)}
WANT = [12, 9, 14, 7, 11]  # tokens a stream: five streams on three slots


def _rows(spans):
    """The exported delivery ring as tuples."""
    flat = array.array("q")
    flat.frombytes(spans["deliveries"])
    width = len(spans["fields"]["deliveries"])
    assert spans["fields"]["deliveries"] == list(stats.DELIVERY_FIELDS) and len(flat) % width == 0
    return [tuple(flat[i:i + width]) for i in range(0, len(flat), width)]


def test_a_record_is_the_request_the_index_and_the_stamps_in_the_order_taken():
    assert stats.DELIVERY_FIELDS == ("rid", "index", "t_emit_ns") + replica_mod.CHUNK_STAMPS
    assert stats.DELIVERY_RING >= 32768 and stats.GC_RING == 256
    assert stats.GC_FIELDS == ("t_start_ns", "duration_ns", "collected")


# ---------------------------------------------------------------------------
# over HTTP: proxy -> replica -> engine, several streams at once
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """What five concurrent SSE streams left behind: the tokens each client
    read and the replica's ``get_stats()["spans"]``."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    got: dict = {}

    def stream(url, i):
        body = {"tokens": [1 + i, 2, 3, 4, 5, 6 + i], "max_new_tokens": WANT[i], "temperature": 0.5 * i, "seed": i}
        req = urllib.request.Request(url, data=json.dumps(body).encode(), headers={"x-request-id": f"stream-{i}"})
        events = urllib.request.urlopen(req, timeout=120).read().decode().split("\n\n")
        got[i] = [json.loads(e[6:])["token"] for e in events if e.startswith("data: {")]

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        serve.start()
        handle = serve.run(serve.deployment(LLMDeployment).bind(MODEL, engine_config=ENGINE), route_prefix="/llm")
        host, port = serve.http_address()
        threads = [threading.Thread(target=stream, args=(f"http://{host}:{port}/llm", i)) for i in range(len(WANT))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        deadline = time.monotonic() + 30
        while True:  # a stream's last batch is recorded as its last poll returns, a moment after the client's read
            counters = ray_tpu.get(handle.get_stats.remote(), timeout=60)
            spans = counters["spans"]
            if len(_rows(spans)) >= sum(WANT) or time.monotonic() > deadline:
                break
            time.sleep(0.05)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    requests = [dict(zip(spans["fields"]["requests"], r)) for r in spans["requests"]]
    by_stream = {}
    for rec in requests:  # "llm-N" of the request ring is N of the delivery ring
        number = int(rec["rid"].rpartition("-")[2])
        by_stream[int(rec["request_id"].rpartition("-")[2])] = sorted(
            (r for r in _rows(spans) if r[D["rid"]] == number), key=lambda r: r[D["index"]]
        )
    return {
        "tokens": got, "spans": spans, "requests": requests, "by_stream": by_stream, "rows": _rows(spans),
        "counters": {k: v for k, v in counters.items() if isinstance(v, int)},
    }


def test_every_streamed_token_has_a_record_and_its_index_runs_from_zero(served):
    assert {i: len(t) for i, t in served["tokens"].items()} == dict(enumerate(WANT))
    assert len(served["rows"]) == sum(WANT) and len(served["requests"]) == len(WANT)
    for i, want in enumerate(WANT):
        recs = served["by_stream"][i]
        assert [r[D["index"]] for r in recs] == list(range(want)), i
    for rec in served["requests"]:  # the request ring's count of the same tokens
        assert rec["generated"] == WANT[int(rec["request_id"].rpartition("-")[2])]


@pytest.mark.parametrize(
    "chain",
    [("t_emit_ns", "t_yield_ns", "t_sweep_ns", "t_got_ns", "t_wrote_ns"), ("t_asked_ns", "t_enter_ns", "t_sweep_ns")],
    ids=["the_token_s_way", "the_poll_that_fetched_it"],
)
def test_the_stamps_are_in_order_wherever_taken(served, chain):
    for r in served["rows"]:
        taken = [r[D[name]] for name in chain if r[D[name]]]
        assert taken == sorted(taken), (chain, r)
        assert all(r[D[name]] > 0 for name in ("t_emit_ns", "t_yield_ns", "t_asked_ns", "t_enter_ns", "t_sweep_ns")), r


def test_the_tokens_of_several_streams_leave_in_one_poll(served):
    """A poll is the proxy's for ALL its streams on the replica: the records
    that share a ``t_enter_ns`` are what it carried, and they share what is
    stamped once a poll (the ask) and once a reply (its arrival at the loop)."""
    by_poll: dict = {}
    for r in served["rows"]:
        by_poll.setdefault(r[D["t_enter_ns"]], []).append(r)
    assert max(len({r[D["rid"]] for r in recs}) for recs in by_poll.values()) > 1
    assert len(by_poll) < len(served["rows"])
    for recs in by_poll.values():
        assert len({r[D["t_asked_ns"]] for r in recs}) == 1
        assert len({r[D["t_got_ns"]] for r in recs if r[D["t_got_ns"]]}) <= 1
        for rid in {r[D["rid"]] for r in recs}:  # a stream's batch is swept at once, its chunks written in order
            mine = [r for r in recs if r[D["rid"]] == rid]
            assert len({r[D["t_sweep_ns"]] for r in mine}) == 1
            assert [r[D["index"]] for r in mine] == list(range(mine[0][D["index"]], mine[0][D["index"]] + len(mine)))
            wrote = [r[D["t_wrote_ns"]] for r in mine]
            assert wrote == sorted(wrote)


def test_the_engine_s_counters_of_the_polls_are_the_records_by_their_t_enter_ns(served):
    """``get_stats()``'s three plain ints (the harness logs a run's integer
    counters): the polls that carried a token, the tokens, and the streams'
    batches; tokens over polls is what one call of the proxy's moved."""
    rows, counters = served["rows"], served["counters"]
    assert counters["stream_poll_chunks"] == sum(WANT) == len(rows)
    assert counters["stream_polls"] == len({r[D["t_enter_ns"]] for r in rows})
    assert counters["stream_poll_streams"] == len({(r[D["rid"]], r[D["t_enter_ns"]]) for r in rows})
    assert counters["stream_polls"] < counters["stream_poll_streams"] <= counters["stream_poll_chunks"]


def _only_the_last_batch_lacks(recs):
    """The records of one stream, by index: those without the proxy's two last
    stamps are of ONE sweep, the stream's last that held a token (its very
    last batch may hold ``[DONE]`` alone: then no token lacks them)."""
    last_sweep = max(r[D["t_sweep_ns"]] for r in recs)
    for r in recs:
        lacks = r[D["t_got_ns"]] == 0
        assert lacks == (r[D["t_wrote_ns"]] == 0), r
        assert not lacks or r[D["t_sweep_ns"]] == last_sweep, r  # no next poll brought them
        assert lacks or r[D["t_sweep_ns"]] <= r[D["t_got_ns"]] <= r[D["t_wrote_ns"]], r
    return [r for r in recs if r[D["t_got_ns"]] == 0]


def test_only_a_request_s_last_batch_lacks_the_proxy_s_two_last_stamps(served):
    for recs in served["by_stream"].values():
        lacking = _only_the_last_batch_lacks(recs)
        assert lacking == recs[len(recs) - len(lacking):]  # the stream's last tokens
    assert any(r[D["t_wrote_ns"]] for r in served["rows"])


def test_the_four_hops_add_up_to_the_way_from_emit_to_socket(served):
    """``deliver_wake`` + ``pickup`` + ``reply`` + ``write`` of a token are
    ``t_wrote_ns - t_emit_ns`` to the nanosecond: the hops share their ends."""
    hops = [("t_emit_ns", "t_yield_ns"), ("t_yield_ns", "t_sweep_ns"), ("t_sweep_ns", "t_got_ns"), ("t_got_ns", "t_wrote_ns")]
    whole = 0
    for r in served["rows"]:
        if r[D["t_wrote_ns"]]:
            assert all(r[D[a]] > 0 and r[D[b]] > 0 for a, b in hops)
            assert sum(r[D[b]] - r[D[a]] for a, b in hops) == r[D["t_wrote_ns"]] - r[D["t_emit_ns"]] > 0
            whole += 1
    assert whole >= sum(WANT) - sum(len([r for r in recs if not r[D["t_got_ns"]]]) for recs in served["by_stream"].values())


def test_tokens_of_one_pass_share_one_stamp_inside_that_pass_s_record(served):
    spans = served["spans"]
    names = spans["fields"]["iterations"]
    flat, width = spans["iterations"], len(names)
    passes = [dict(zip(names, flat[i:i + width])) for i in range(0, len(flat), width)]
    by_emit: dict = {}
    for r in served["rows"]:
        by_emit.setdefault(r[D["t_emit_ns"]], []).append(r)
    for t_emit, recs in by_emit.items():
        (it,) = [p for p in passes if p["t_start_ns"] <= t_emit <= p["t_start_ns"] + p["llm.iteration"]]
        assert len({r[D["rid"]] for r in recs}) == len(recs)  # one token a stream a pass
        # a decode step's rows share its llm.emit, and with them the first token of a prompt whose
        # last chunk rode that step; a last chunk that ran alone has an llm.emit of its own
        firsts = sum(r[D["index"]] == 0 for r in recs)
        assert firsts <= 1 and len(recs) <= max(it["rows"] + firsts, 1), (it, recs)
    assert max(len(recs) for recs in by_emit.values()) > 1  # streams decoded together
    assert len(by_emit) < len(served["rows"])


def test_the_collector_s_records_leave_with_the_rings(served):
    spans = served["spans"]
    assert spans["fields"]["gc"] == list(stats.GC_FIELDS)
    assert all(len(r) == 3 and r[0] > 0 and r[1] > 0 for r in spans["gc"])
    younger = spans["gc_younger"]
    assert set(younger) == {"collections", "ns"} and all(len(v) == 2 for v in younger.values())
    assert younger["collections"][0] > 0 and younger["ns"][0] > 0  # a replica allocates


# ---------------------------------------------------------------------------
# the chunk stamps, with this process as the proxy
# ---------------------------------------------------------------------------


def _numbers(request):
    from ray_tpu.serve.api import StreamingResponse

    kw = {"on_delivered": DELIVERED.append} if request.headers.get("stamps") else {}
    return StreamingResponse((f"n{i};" for i in range(6)), content_type="text/plain", **kw)


def _held(request):
    from ray_tpu.serve.api import StreamingResponse

    def gen():
        yield "n0;"
        HOLD.wait(timeout=60)
        yield "n1;"

    return StreamingResponse(gen(), content_type="text/plain", on_delivered=DELIVERED.append)


DELIVERED: list = []
HOLD = threading.Event()


def _open(callable_, headers=None, body=b""):
    rep = replica_mod.Replica(pickle.dumps((callable_, (), {})))
    env = rep.handle_http_request("GET", "/", {}, body, headers or {})
    return rep, env["__serve_stream__"]


def _drain(rep, sid, stamped: bool, foreign: str = ""):
    """Polls the stream to its end as ``asgi._pump_stream`` does; returns the
    chunks. ``foreign``: the proxy stamp that is of another host's clock."""
    off = {name: 0 for name in ("t_asked_ns", "t_got_ns", "t_wrote_ns")}
    if foreign:
        off[foreign] = int(2 * stats.FOREIGN_STAMP_S * 1e9)
    chunks, t_got, wrote = [], 0, []
    while True:
        if stamped:
            batch = rep.next_stream_chunk((sid, time.monotonic_ns() + off["t_asked_ns"], t_got, wrote))
        else:
            batch = rep.next_stream_chunk(sid)
        t_got, wrote = time.monotonic_ns() + off["t_got_ns"], []
        for chunk in batch["chunks"]:
            chunks.append(chunk)
            wrote.append(time.monotonic_ns() + off["t_wrote_ns"])
        if batch["done"]:
            return chunks


@pytest.mark.parametrize("callback", [False, True], ids=["no_callback", "callback"])
@pytest.mark.parametrize("stamped", [False, True], ids=["bare_poll", "stamped_poll"])
def test_a_stream_gives_the_same_chunks_with_and_without_the_stamps(stamped, callback):
    """``next_stream_chunk(sid)`` as ``_migrate_stream``'s first poll and the
    stream tests call it, and a ``StreamingResponse`` without the callback."""
    del DELIVERED[:]
    rep, sid = _open(_numbers, {"stamps": "1"} if callback else {})
    assert b"".join(_drain(rep, sid, stamped)) == b"n0;n1;n2;n3;n4;n5;"
    assert rep.next_stream_chunk(sid) is None  # the stream is gone, as before
    stamps = [s for batch in DELIVERED for s in batch]
    assert len(stamps) == (6 if callback else 0)
    for s in stamps:
        t_yield, t_asked, t_enter, t_sweep, t_got, t_wrote = s
        assert len(s) == len(replica_mod.CHUNK_STAMPS) and 0 < t_yield <= t_sweep and 0 < t_enter <= t_sweep
        assert (t_asked > 0) == stamped and (t_got == 0) == (t_wrote == 0)
        if t_got:
            assert t_asked <= t_enter and t_sweep <= t_got <= t_wrote
    if callback:  # only a last batch has no next poll; without stamps from the proxy no batch has them
        lacking = [s for s in stamps if s[4:] == (0, 0)]
        assert all(s[3] == stamps[-1][3] for s in lacking) and (stamped or lacking == stamps)


def test_a_cancelled_stream_s_last_batch_is_handed_over_without_the_proxy_s_stamps():
    del DELIVERED[:]
    HOLD.clear()
    rep, sid = _open(_held)
    batch = {"chunks": []}
    while not batch["chunks"]:  # a poll gives up after half a second
        batch = rep.next_stream_chunk((sid, time.monotonic_ns(), 0, ()))
    assert batch == {"chunks": [b"n0;"], "done": False} and not DELIVERED  # the proxy's stamps may still come
    rep.cancel_stream(sid)
    HOLD.set()
    assert len(DELIVERED) == 1 and len(DELIVERED[0]) == 1 and DELIVERED[0][0][4:] == (0, 0)


@pytest.fixture(scope="module")
def llm_replica():
    """A replica of ``LLMDeployment`` in this process: its pump threads, its
    ``next_stream_chunk`` and the collector's hook are the test's own."""
    from ray_tpu.serve.llm import LLMDeployment

    rep = replica_mod.Replica(pickle.dumps((LLMDeployment, (MODEL,), {"engine_config": ENGINE})))
    yield rep
    rep.prepare_for_shutdown()


def _llm_stream(rep, n, **drain):
    body = json.dumps({"tokens": [3, 1, 4, 1, 5, 9], "max_new_tokens": n}).encode()
    env = rep.handle_http_request("POST", "/llm", {}, body, {})
    before = rep._callable.engine.spans.deliveries.n
    chunks = _drain(rep, env["__serve_stream__"], True, **drain)
    assert chunks[-1] == b"data: [DONE]\n\n" and len(chunks) == n + 1
    return _rows(rep._callable.get_stats()["spans"])[before - rep._callable.engine.spans.deliveries.n:]


@pytest.mark.parametrize("foreign", ["t_asked_ns", "t_got_ns", "t_wrote_ns"])
def test_a_proxy_on_another_host_s_clock_leaves_no_proxy_stamp(llm_replica, foreign):
    recs = _llm_stream(llm_replica, 6, foreign=foreign)
    assert [r[D["index"]] for r in recs] == list(range(6))
    for r in recs:
        # the last batch's two last stamps never come: a t_asked_ns alone cannot be told from this host's
        if foreign == "t_asked_ns" or r[D["t_sweep_ns"]] != recs[-1][D["t_sweep_ns"]]:
            assert r[D["t_asked_ns"]] == 0
        assert r[D["t_got_ns"]] == r[D["t_wrote_ns"]] == 0
        assert 0 < r[D["t_emit_ns"]] <= r[D["t_yield_ns"]] <= r[D["t_sweep_ns"]] and r[D["t_enter_ns"]] > 0
    own = _llm_stream(llm_replica, 6)  # and a proxy on this host's clock keeps its stamps
    assert all(r[D["t_asked_ns"]] for r in own)
    _only_the_last_batch_lacks(own)


def test_an_echoed_resume_token_and_the_done_event_are_no_records(llm_replica):
    body = json.dumps({"tokens": [2, 7, 1, 8], "max_new_tokens": 5, "resume_tokens": [9], "echo_resume": True}).encode()
    env = llm_replica.handle_http_request("POST", "/llm", {}, body, {})
    ring = llm_replica._callable.engine.spans.deliveries
    before = ring.n
    chunks = _drain(llm_replica, env["__serve_stream__"], True)
    assert len(chunks) == 1 + 4 + 1  # the echo, the engine's four, [DONE]
    assert ring.n - before == 4


@pytest.mark.parametrize("size", [8, stats.DELIVERY_RING], ids=["8", "the_engine_s"])
def test_the_ring_wraps_at_its_size(size):
    ring = stats.DeliveryRing(size) if size == 8 else stats.EngineSpans().deliveries
    assert ring.size == size
    now = time.monotonic_ns()
    rec = lambda i: (7, i) + tuple(now + i + k for k in range(7))  # noqa: E731
    assert ring.export() == b""
    ring.push([rec(i) for i in range(size - 3)])
    assert len(ring.export()) == 8 * len(D) * (size - 3)  # not full: what it holds
    for lo in range(size - 3, 2 * size + 5, 5):  # in batches that straddle the end
        ring.push([rec(i) for i in range(lo, min(lo + 5, 2 * size + 5))])
    flat = array.array("q")
    flat.frombytes(ring.export())
    assert len(flat) == size * len(D) and ring.n == 2 * size + 5
    assert list(flat[D["index"]::len(D)]) == list(range(size + 5, 2 * size + 5))  # the newest, oldest first
    assert tuple(flat[-len(D):]) == rec(2 * size + 4)


def test_the_ring_counts_a_poll_once_however_many_streams_and_however_late_their_batches():
    ring = stats.DeliveryRing(64)
    now = time.monotonic_ns()
    rec = lambda rid, i, enter: (rid, i, now, now, now, enter, now, now, now)  # noqa: E731
    assert len(rec(0, 0, 0)) == len(D) and D["t_enter_ns"] == 5
    ring.push([rec(1, 0, now + 1), rec(1, 1, now + 1)])  # poll 1: two tokens of stream 1
    ring.push([rec(2, 0, now + 1)])  # and one of stream 2
    ring.push([])  # a batch without a token of the engine's is no batch
    ring.push([rec(2, 1, now + 2)])  # poll 2
    ring.push([rec(3, 0, now + 1)])  # poll 1's third stream, handed over after poll 2's
    assert (ring.polls, ring.batches, ring.n) == (2, 4, 5)


@pytest.mark.parametrize("generation", [2, 0])
def test_a_forced_collection_is_on_the_record(generation):
    stats.listen_for_gc()
    stats.listen_for_gc()  # once a process
    assert gc.callbacks.count(stats._on_gc) == 1
    held = len(stats.gc_records())
    pushed, young = stats.GC_PAUSES.n, stats.GC_YOUNGER["collections"][0]
    t0 = time.monotonic_ns()
    gc.collect(generation)
    t1 = time.monotonic_ns()
    exported = stats.EngineSpans().export()
    if generation == 2:  # another thread of this process may collect too: at least this one
        assert stats.GC_PAUSES.n >= pushed + 1 and len(exported["gc"]) >= min(held + 1, stats.GC_RING)
        mine = [r for r in exported["gc"] if t0 <= r[0] <= r[0] + r[1] <= t1]
        assert mine and all(len(r) == 3 and r[2] >= 0 for r in mine)
    else:
        assert exported["gc_younger"]["collections"][0] >= young + 1 and exported["gc_younger"]["ns"][0] > 0


# ---------------------------------------------------------------------------
# the profiler's host plane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def host_plane(llm_replica, tmp_path_factory):
    """name -> [(thread line, start, end)] of the events this PR adds, in the
    ``/host:CPU`` plane of a profile taken round two streams and a collection."""
    import jax
    from jax.profiler import ProfileData

    log_dir = str(tmp_path_factory.mktemp("profile"))
    _llm_stream(llm_replica, 3)  # programs are built
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        _llm_stream(llm_replica, 5)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):  # a line a thread
                for e in line.events:
                    events.setdefault(e.name, []).append((thread, e.start_ns, e.start_ns + e.duration_ns))
    return events


@pytest.mark.parametrize("name, at_least", [("llm.sse.event", 5), ("serve.stream.sweep", 1), ("gc.gen2", 1)])
def test_a_profile_holds_the_new_host_work_beside_the_engine_s_spans(host_plane, name, at_least):
    assert "llm.iteration" in host_plane and "llm.emit" in host_plane
    assert len(host_plane.get(name, ())) >= at_least, sorted(host_plane)
    if name == "llm.sse.event":  # the stream's own thread, not the scheduler's
        assert {line for line, _, _ in host_plane[name]}.isdisjoint({line for line, _, _ in host_plane["llm.emit"]})

"""Self-healing LLM serving (ISSUE 14): mid-stream request migration with
teacher-forced resumption after a seeded replica kill, drain-before-retire
under rolling updates, and the assign->dead-replica handle reassign.

Layout (tier-1 budget): ONE module-scoped single-node cluster + serve
instance + 2-replica LLMDeployment hosts everything; the seeded-sampling
migration arm and the rolling-update drain oracle are marked `slow` (each
spawns extra replica processes); the greedy migration oracle — THE tentpole
acceptance test — runs in tier-1.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve._private.common import CONTROLLER_NAME

MODEL = dict(
    vocab_size=64,
    d_model=32,
    n_layers=1,
    n_heads=2,
    n_kv_heads=2,
    d_ff=48,
    max_seq_len=64,
    dtype="float32",
    remat=False,
)
ENGINE = dict(num_slots=4, block_size=4, max_model_len=64, prefill_chunk=4)


@pytest.fixture(scope="module")
def ft_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=6, object_store_memory=96 * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        serve.start()
        yield cluster
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


@pytest.fixture(scope="module")
def llm_app(ft_cluster):
    from ray_tpu.serve.llm import LLMDeployment

    app = serve.deployment(num_replicas=2, version="v1")(LLMDeployment).bind(
        MODEL, engine_config=dict(ENGINE)
    )
    handle = serve.run(app, route_prefix="/llm")
    return ft_cluster, handle


def _oracle(prompt, n, **sampling):
    """Uninterrupted reference run on a LOCAL engine with the same
    seed-deterministic params the replicas build (init_seed=0)."""
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    kw = dict(MODEL)
    import jax.numpy as jnp

    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    cfg = TransformerConfig(**kw)
    eng = LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, **ENGINE)
    try:
        return eng.submit(prompt, max_new_tokens=n, **sampling).result(120)
    finally:
        eng.shutdown()


def _replica_actors():
    """actor_name list for the llm deployment, from the controller table."""
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    table = ray_tpu.get(controller.get_routing_table.remote(-2, 0.1))["table"]
    entry = table.get("LLMDeployment") or {}
    return [r["actor_name"] for r in entry.get("replicas", [])]


def _stream_sse(url, body, toks, events, timeout=300):
    """POST one streaming request and drain its SSE events."""
    req = urllib.request.Request(url, data=json.dumps(body).encode())
    return _stream_sse_resp(urllib.request.urlopen(req, timeout=timeout), toks, events)


def _stream_sse_resp(resp, toks, events):
    """Read one SSE stream incrementally; tokens append into `toks` as they
    arrive (so callers can act mid-stream); events records (t, kind)."""
    buf = b""
    while True:
        chunk = resp.read(64)
        if not chunk:
            return False
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            if not event.startswith(b"data: "):
                continue
            payload = event[6:]
            if payload == b"[DONE]":
                events.append((time.monotonic(), "done"))
                return True
            toks.append(json.loads(payload)["token"])
            events.append((time.monotonic(), "token"))


def _flight_events(cluster, kind, since_wall):
    io_events = []
    from ray_tpu._private.rpc import EventLoopThread

    resp = EventLoopThread.get().run(cluster.nodes[0].rpc_debug_dump({}), timeout=15)
    for proc in resp.get("processes", []):
        for ev in proc.get("events", []):
            if ev.get("type") == kind and ev.get("ts", 0) >= since_wall - 2.0:
                io_events.append(ev)
    return io_events


def _run_migration_oracle(llm_app, prompt, n, sampling):
    """Kill the serving replica mid-stream with a SEEDED plan; the stream
    must resume on another replica and the client must see the byte-exact
    uninterrupted token sequence, nothing re-emitted, nothing dropped.

    The victim is PRE-PICKED: the request carries its prefix routing hint,
    which pins it to replicas[crc32(hint) % n] — so the kill plan can be
    armed in that replica's process BEFORE the request, and the kill point
    (the 3rd actor-call response after install: the request accept + 2
    stream-chunk pumps) is seeded and replayable."""
    import zlib

    from ray_tpu.serve._private.common import PREFIX_HINT_HEADER
    from ray_tpu.serve.llm import prefix_route_hint

    cluster, _handle = llm_app
    expect = _oracle(prompt, n, **sampling)
    host, port = serve.http_address()
    t_wall0 = time.time()
    hint = prefix_route_hint(prompt, ENGINE["block_size"])
    assert hint
    # A previous kill's replacement may still be booting; the victim pick
    # needs the full 2-replica table.
    deadline = time.monotonic() + 180
    actors = _replica_actors()
    while len(actors) < 2 and time.monotonic() < deadline:
        time.sleep(0.25)
        actors = _replica_actors()
    assert len(actors) == 2, actors
    victim = actors[zlib.crc32(hint.encode()) % len(actors)]
    assert cluster.install_plan_in_actor(
        victim,
        {"rules": [{"kind": "kill", "method": ["actor_call"],
                    "side": "resp", "after": 2, "times": 1}]},
        seed=13,
    )
    toks: list = []
    events: list = []
    body = dict(tokens=prompt, max_new_tokens=n, **sampling)
    req = urllib.request.Request(
        f"http://{host}:{port}/llm",
        data=json.dumps(body).encode(),
        headers={PREFIX_HINT_HEADER: hint},
    )
    done = _stream_sse_resp(urllib.request.urlopen(req, timeout=240), toks, events)
    assert done, "stream ended without [DONE]"
    assert toks == expect, (toks, expect)
    # The proxy recorded the migration; the victim's last words are the
    # chaos_kill event in its (SIGKILL-surviving) flight ring.
    assert _flight_events(cluster, "llm_migrate", t_wall0), "no migration recorded"
    assert _flight_events(cluster, "chaos_kill", t_wall0), "no kill recorded"
    # Leak oracle: every LIVE replica's KV pool is back to full.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = []
        for name in _replica_actors():
            try:
                stats.append(ray_tpu.get(
                    ray_tpu.get_actor(name).handle_request.remote(
                        "get_stats", (), {}
                    ),
                    timeout=15,
                ))
            except Exception:
                pass
        if stats and all(
            s["free_blocks"] + s["cached_blocks"] == s["num_blocks"] for s in stats
        ):
            return
        time.sleep(0.25)
    pytest.fail(f"surviving replicas leaked KV blocks: {stats}")


def test_midstream_kill_migrates_greedy(llm_app):
    """THE tentpole oracle, greedy arm: a replica SIGKILLed mid-decode by a
    seeded plan; the proxy resubmits with resume_tokens= and the client's
    token sequence is byte-identical to an uninterrupted run."""
    _run_migration_oracle(
        llm_app, prompt=[3, 1, 4, 1, 5, 9, 2, 6], n=24, sampling={}
    )


class Counting:
    """Streams ``n`` SSE tokens 0..n-1, one every 50 ms, and resumes after the
    tokens a client already has, as ``LLMDeployment`` does."""

    def __call__(self, request):
        import os

        from ray_tpu.serve.api import StreamingResponse

        body = request if isinstance(request, dict) else request.json()
        if body.get("pid"):
            return os.getpid()
        have = len(body.get("resume_tokens") or ())

        def sse():
            for tok in range(have, int(body["n"])):
                time.sleep(0.05)
                yield f"data: {json.dumps({'token': tok})}\n\n"
            yield "data: [DONE]\n\n"

        return StreamingResponse(
            sse(), content_type="text/event-stream",
            resume={"kind": "sse_tokens", "body": {"n": body["n"]}},
        )


def test_a_replica_killed_under_a_shared_poll_migrates_every_stream_it_carried(ft_cluster):
    """The proxy polls its streams on a replica in ONE call: the call that
    fails with the replica gone fails each of them, and each migrates by
    itself, within ``_MAX_MIGRATIONS`` as ever, re-emitting and dropping
    nothing."""
    import os
    import signal
    import zlib

    from ray_tpu.serve._private.asgi import ProxyASGIApp
    from ray_tpu.serve._private.common import PREFIX_HINT_HEADER

    assert ProxyASGIApp._MAX_MIGRATIONS == 2
    serve.run(serve.deployment(num_replicas=2)(Counting).bind(), route_prefix="/count")
    host, port = serve.http_address()
    url = f"http://{host}:{port}/count"
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        table = ray_tpu.get(controller.get_routing_table.remote(-2, 0.1))["table"]
        actors = [r["actor_name"] for r in (table.get("Counting") or {}).get("replicas", [])]
        if len(actors) == 2:
            break
        time.sleep(0.25)
    assert len(actors) == 2, actors
    hint = "one-replica-for-all"
    victim = actors[zlib.crc32(hint.encode()) % len(actors)]
    pid = ray_tpu.get(
        ray_tpu.get_actor(victim).handle_request.remote("__call__", ({"pid": 1},), {}), timeout=60
    )
    t_wall0 = time.time()
    n, streams = 60, 4
    toks = [[] for _ in range(streams)]
    done = [False] * streams

    def read(i):
        req = urllib.request.Request(
            url, data=json.dumps({"n": n}).encode(), headers={PREFIX_HINT_HEADER: hint}
        )
        done[i] = _stream_sse_resp(urllib.request.urlopen(req, timeout=240), toks[i], [])

    threads = [threading.Thread(target=read, args=(i,), daemon=True) for i in range(streams)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while min(len(t) for t in toks) < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert min(len(t) for t in toks) >= 5, toks  # all four stream from the victim, in one poll
    os.kill(pid, signal.SIGKILL)
    for t in threads:
        t.join(timeout=240)
    assert all(done), done
    assert toks == [list(range(n))] * streams
    assert len(_flight_events(ft_cluster, "llm_migrate", t_wall0)) >= streams
    serve.delete("Counting")


@pytest.mark.slow
def test_midstream_kill_migrates_seeded_sampling(llm_app):
    """Sampled arm: the counter-based per-request RNG stream makes the
    migrated continuation bit-identical too."""
    _run_migration_oracle(
        llm_app,
        prompt=[2, 7, 1, 8, 2, 8, 1, 8],
        n=24,
        sampling=dict(temperature=0.9, top_k=16, seed=11),
    )


@pytest.mark.slow
def test_rolling_update_drains_streams(llm_app):
    """Drain oracle: a rolling update (v1 -> v2) under a CLOSED LOOP of
    concurrent streams completes with ZERO dropped streams and every
    stream's tokens matching the oracle — streams that straddle a retire
    finish on the draining replica; new requests land on live ones (the
    proxy reassigns across the drain-refusal race)."""
    from ray_tpu.serve.llm import LLMDeployment

    cluster, _handle = llm_app
    host, port = serve.http_address()
    t_wall0 = time.time()
    n = 32
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, 6).tolist() for _ in range(3)]
    oracles = [_oracle(p, n) for p in prompts]
    stop = threading.Event()
    failures: list = []
    completions = [0]

    def closed_loop(i):
        while not stop.is_set():
            toks: list = []
            try:
                done = _stream_sse(
                    f"http://{host}:{port}/llm",
                    dict(tokens=prompts[i], max_new_tokens=n),
                    toks, [],
                )
                assert done, "stream ended without [DONE]"
                assert toks == oracles[i], (toks, oracles[i])
                completions[0] += 1
            except Exception as e:  # noqa: BLE001
                failures.append(f"stream {i}: {type(e).__name__}: {e}")
                return

    threads = [
        threading.Thread(target=closed_loop, args=(i,), daemon=True)
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while completions[0] < 2 and not failures and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not failures, failures
    # Roll to v2 while the loop keeps streaming. serve.run blocks until
    # the new version covers the target (old replicas drain in background).
    app2 = serve.deployment(num_replicas=2, version="v2")(LLMDeployment).bind(
        MODEL, engine_config=dict(ENGINE)
    )
    serve.run(app2, route_prefix="/llm")
    time.sleep(1.0)  # a few post-update iterations
    stop.set()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert not failures, f"dropped/corrupt streams across the update: {failures}"
    st = serve.status()["LLMDeployment"]
    assert st["version"] == "v2"
    # The drains were recorded (begin + a terminal outcome per old replica).
    drains = [e["detail"] for e in _flight_events(cluster, "replica_drain", t_wall0)]
    assert any(d.endswith(":begin") for d in drains), drains
    assert any(
        d.split(":", 1)[1] in ("clean", "timeout") for d in drains
    ), drains


def test_handle_reassigns_off_dead_replica(ft_cluster):
    """Satellite: a non-streaming handle call assigned to a replica that
    died before accepting transparently reassigns ONCE (bounded) instead of
    surfacing raw ActorDiedError — pinned on a bare Router with a stale
    hand-fed table that still lists the corpse."""
    import os as _os

    from ray_tpu.serve._private.router import Router
    from ray_tpu.serve.handle import DeploymentHandle

    class FakeReplica:
        def handle_request(self, method, args, kwargs, multiplexed_model_id=""):
            return f"pong-{_os.getpid()}"

    a = ray_tpu.remote(name="ftrep-a")(FakeReplica).remote()
    b = ray_tpu.remote(name="ftrep-b")(FakeReplica).remote()
    try:
        ray_tpu.get(a.handle_request.remote("__call__", (), {}), timeout=60)
        ray_tpu.get(b.handle_request.remote("__call__", (), {}), timeout=60)
        ray_tpu.kill(a)
        # Wait until the GCS reflects the death (the probe's source of truth).
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                ray_tpu.get_actor("ftrep-a")
                time.sleep(0.1)
            except Exception:
                break
        router = Router(None)
        router._table = {
            "dep": {
                "replicas": [
                    {"replica_id": "ra", "actor_name": "ftrep-a",
                     "max_concurrent_queries": 10},
                    {"replica_id": "rb", "actor_name": "ftrep-b",
                     "max_concurrent_queries": 10},
                ],
                "route_prefix": None,
            }
        }
        router._rr["dep"] = 0  # round-robin picks the corpse first
        handle = DeploymentHandle("dep", router)
        out = ray_tpu.get(handle.remote(), timeout=60)
        assert out.startswith("pong-")
        # The dead replica's claimed slot was released on reassign.
        assert router._inflight.get("ftrep-a", 0) == 0
    finally:
        for h in (a, b):
            try:
                ray_tpu.kill(h)
            except Exception:
                pass

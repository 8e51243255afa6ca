"""Replica actor (reference: python/ray/serve/_private/replica.py:384
RayServeReplica, handle_request at :639).

Each replica is a dedicated actor process wrapping the user callable. On a
TPU node a replica can pin the chip and hold a jit-compiled model — the
TPU-native serving idiom: one replica per chip, XLA-compiled predict, queue
depth reported to the controller for autoscaling.
"""

from __future__ import annotations

import contextlib
import logging
import pickle
import queue as _queue
import sys
import threading
import time
import traceback

logger = logging.getLogger(__name__)

# A streamed response leaves by POLLS, and the unit of a poll is "one proxy's
# streams on this replica", not one stream (``Replica.next_stream_chunks``):
# the call names every stream its proxy can take chunks for, waits until ANY
# of them has something, sweeps each that has and returns the batches by
# stream id. A proxy keeps one such call in flight, so the calls a second
# follow the producers' passes and not passes x streams. One stream is the
# one-element case (``next_stream_chunk``).
#
# What is stamped on every chunk of a streamed response, in the order taken
# (``time.monotonic_ns()``, 0 = not taken): by the pump thread as the chunk's
# bytes are about to be queued; by the proxy as it hands the poll that will
# fetch the chunk to its executor (part of that poll's argument, one stamp for
# all the streams the poll names); by ``next_stream_chunks`` on its first line
# (one stamp a poll: the chunks of every stream a poll carried share it, and
# no other poll has it) and as it has swept the stream's batch, just before it
# returns; by the proxy as the poll's reply reaches its event loop (one stamp
# for the reply's batches) and as the chunk's ``send`` returns. The proxy's
# two last ride back on the NEXT poll that names the stream, so a stream's
# last batch never gets them. A response that gives
# ``StreamingResponse.on_delivered`` is handed these, one tuple a chunk.
CHUNK_STAMPS = ("t_yield_ns", "t_asked_ns", "t_enter_ns", "t_sweep_ns", "t_got_ns", "t_wrote_ns")

# A replica's own way from the controller's decision to its first answer, on
# the same clock, in the order taken (0 = not taken): the controller as it asks
# the actor manager for the replica (it rides in the actor's keyword arguments);
# the worker process's ``main`` on its first line; ``Replica.__init__`` on its
# first line and on the line before it calls the deployment's class; and
# ``check_health`` as it first answers. One replica a process: one dict.
SETUP_STAMPS = {"t_requested_ns": 0, "t_process_ns": 0, "t_actor_ns": 0, "t_callable_ns": 0, "t_ready_ns": 0}


# A poll that finds nothing waits this long for a chunk of any of its streams.
_POLL_WAIT_S = 0.5


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` where this process has loaded jax
    (only then can a profile of it be taken; the span then lies in its
    ``/host:CPU`` plane), inert until one is; nothing elsewhere."""
    jax = sys.modules.get("jax")
    return jax.profiler.TraceAnnotation(name) if jax is not None else contextlib.nullcontext()


class _StreamPump:
    """Runs one response stream's generator on a dedicated thread,
    prefetching into a bounded queue. The replica's RPC surface only ever
    drains the queue with a short timeout, so a producer that stalls inside
    its generator cannot head-of-line-block the replica's task slots (and a
    disconnected client's pump dies on cancel, not the 5-minute reap).
    ``ready`` is the replica's condition: notified after every put, it wakes
    the polls that wait for a chunk of any of their streams."""

    def __init__(self, gen, model_id: str, ready: threading.Condition, on_cancel=None, on_delivered=None):
        self.gen = gen
        self.model_id = model_id
        self.ready = ready
        self.on_cancel = on_cancel
        self.on_delivered = on_delivered
        self.q: _queue.Queue = _queue.Queue(maxsize=8)  # backpressure bound
        self.cancelled = threading.Event()
        self.last_pump = time.time()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _put(self, item) -> bool:
        while not self.cancelled.is_set():
            try:
                self.q.put(item, timeout=0.25)
            except _queue.Full:
                continue
            with self.ready:
                self.ready.notify_all()
            return True
        return False

    def _run(self):
        from ray_tpu.serve.multiplex import _set_multiplexed_model_id

        # The generator body runs HERE: scope the multiplexed model id to
        # this thread so concurrent requests can't bleed theirs in.
        _set_multiplexed_model_id(self.model_id)
        try:
            for item in self.gen:
                chunk = _encode_chunk(item)
                if not self._put(("chunk", chunk, time.monotonic_ns())):  # t_yield_ns
                    break
            else:
                self._put(("done", None, 0))
        except BaseException as e:  # delivered to the consumer, then re-raised
            self._put(("error", e, 0))
        finally:
            try:
                self.gen.close()
            except Exception:
                pass

    def cancel(self):
        self.cancelled.set()
        # Producer-side teardown (StreamingResponse.on_disconnect) fires
        # HERE, synchronously: the generator thread may be parked inside
        # its producer (e.g. the LLM engine's token queue) and only
        # observes `cancelled` at its next yield — resources like decode
        # slots and KV blocks must not wait for that. dict.pop is
        # GIL-atomic, so concurrent cancel()s fire the callback once.
        cb = self.__dict__.pop("on_cancel", None)
        if cb is not None:
            try:
                cb()
            except Exception:
                pass
        self.delivered()  # the batch swept last gets no next poll

    def swept(self, yields: list, t_asked_ns: int, t_enter_ns: int):
        """A poll is about to return the chunks stamped ``yields``: kept
        until the proxy's stamps of the batch come back (``delivered``)."""
        if self.on_delivered is not None and yields:
            self._swept = (yields, t_asked_ns, t_enter_ns, time.monotonic_ns())

    def delivered(self, t_got_ns: int = 0, wrote_ns=()):
        """Hands the response's owner the ``CHUNK_STAMPS`` of the batch swept
        last, one tuple a chunk in order: with the proxy's two last where the
        stream's next poll brought them, with 0 where none will come."""
        batch = self.__dict__.pop("_swept", None)  # GIL-atomic, as in cancel
        if batch is None:
            return
        yields, t_asked_ns, t_enter_ns, t_sweep_ns = batch
        if len(wrote_ns) != len(yields):  # no stamps, or not this batch's
            t_got_ns, wrote_ns = 0, [0] * len(yields)
        try:
            self.on_delivered(
                [(y, t_asked_ns, t_enter_ns, t_sweep_ns, t_got_ns, w) for y, w in zip(yields, wrote_ns)]
            )
        except Exception:
            pass


class Replica:
    def __init__(
        self,
        import_spec: bytes,
        user_config=None,
        deployment_name: str = "",
        replica_id: str = "",
        controller_name: str = "",
        t_requested_ns: int = 0,
    ):
        from ray_tpu._private import worker_context
        from ray_tpu.serve._private.common import HandleMarker

        SETUP_STAMPS.update(
            t_requested_ns=t_requested_ns, t_process_ns=worker_context.T_PROCESS_NS,
            t_actor_ns=time.monotonic_ns(), t_callable_ns=0, t_ready_ns=0,
        )

        cls_or_fn, init_args, init_kwargs = pickle.loads(import_spec)

        def materialize(v):
            if isinstance(v, HandleMarker):
                # Composition: a bound child deployment becomes a live handle.
                from ray_tpu.serve.api import get_deployment_handle

                return get_deployment_handle(v.deployment_name)
            if isinstance(v, list):
                return [materialize(x) for x in v]
            if isinstance(v, tuple):
                return tuple(materialize(x) for x in v)
            if isinstance(v, dict):
                return {k: materialize(x) for k, x in v.items()}
            return v

        init_args = tuple(materialize(a) for a in init_args)
        init_kwargs = {k: materialize(v) for k, v in init_kwargs.items()}
        if isinstance(cls_or_fn, type):
            SETUP_STAMPS["t_callable_ns"] = time.monotonic_ns()
            self._callable = cls_or_fn(*init_args, **init_kwargs)
        else:
            self._callable = cls_or_fn
        self._is_function = not isinstance(cls_or_fn, type)
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        self._streams: dict = {}
        self._stream_counter = 0
        # The pumps notify it after every put; polls wait on it.
        self._chunks_ready = threading.Condition()
        # poller -> the newest of its polls that was told to return at once.
        self._poll_kicks: dict = {}
        # Polls that carried a chunk, and the chunks and streams they carried.
        self._stream_polls = self._stream_poll_chunks = self._stream_poll_streams = 0
        self._draining = False
        self._deployment_name = deployment_name
        self._replica_id = replica_id
        if user_config is not None:
            self.reconfigure(user_config)
        # Autoscaling metrics PUSH (reference: autoscaling_metrics.py —
        # replicas report their own queue depth). A dedicated daemon thread,
        # NOT an actor method: actor calls share the request thread pool, so
        # a polled metric could only run when a slot freed — biased low by
        # construction.
        if deployment_name and controller_name:
            self._metrics_stop = threading.Event()

            def _push_loop():
                import ray_tpu

                controller = None
                while not self._metrics_stop.wait(1.0):
                    try:
                        if controller is None:
                            controller = ray_tpu.get_actor(controller_name)
                        controller.record_metrics.remote(
                            deployment_name, replica_id, self._ongoing
                        )
                    except Exception:
                        controller = None  # controller restarting; re-resolve

            threading.Thread(
                target=_push_loop, name="replica-metrics", daemon=True
            ).start()

    def reconfigure(self, user_config):
        """Push a new user_config without restarting (reference:
        deployment_state version/user_config rolling update)."""
        fn = getattr(self._callable, "reconfigure", None)
        if fn is not None:
            fn(user_config)
        return True

    def handle_request(
        self, method_name: str, args: tuple, kwargs: dict, multiplexed_model_id: str = ""
    ):
        from ray_tpu.serve.multiplex import _set_multiplexed_model_id

        with self._lock:
            if self._draining:
                # Drain-before-retire: NEW requests are refused with the
                # typed error (proxy/handle reassign on it); in-flight
                # requests and live stream pumps keep running to completion.
                from ray_tpu.exceptions import ReplicaDrainingError

                raise ReplicaDrainingError(
                    deployment=self._deployment_name,
                    replica_id=self._replica_id,
                )
            self._ongoing += 1
            self._total += 1
        try:
            _set_multiplexed_model_id(multiplexed_model_id)
            if self._is_function or method_name == "__call__":
                target = self._callable
            else:
                target = getattr(self._callable, method_name)
            return target(*args, **kwargs)
        finally:
            with self._lock:
                self._ongoing -= 1

    def handle_http_request(
        self,
        method: str,
        path: str,
        query: dict,
        body: bytes,
        headers: dict,
        multiplexed_model_id: str = "",
        route_prefix: str | None = None,
        raw_query_string: str | None = None,
    ):
        """HTTP entry: the callable gets a lightweight Request object. The
        proxy passes the multiplexed model id it already extracted for
        routing — one extraction, no divergence — the matched route
        prefix so sub-route dispatch (DAGDriver) works under any mount, and
        the raw query string so ASGI ingress apps see wire-exact bytes."""
        request = HTTPRequest(
            method=method, path=path, query=query, body=body, headers=headers,
            route_prefix=route_prefix, raw_query_string=raw_query_string,
        )
        result = self.handle_request(
            "__call__", (request,), {}, multiplexed_model_id=multiplexed_model_id
        )
        import inspect

        from ray_tpu.serve.api import StreamingResponse

        if isinstance(result, StreamingResponse) or inspect.isgenerator(result):
            # Chunked/SSE responses (reference: serve streaming responses):
            # the generator stays alive here; the proxy pumps it via
            # next_stream_chunks and writes chunks to the socket as produced.
            if isinstance(result, StreamingResponse):
                gen, ctype = iter(result.iterator), result.content_type
                status = getattr(result, "status", 200)
                extra = getattr(result, "headers", None) or {}
                on_cancel = getattr(result, "on_disconnect", None)
                on_delivered = getattr(result, "on_delivered", None)
                resume = getattr(result, "resume", None)
            else:
                gen, ctype = result, "application/octet-stream"
                status, extra = 200, {}
                on_cancel = on_delivered = resume = None
            with self._lock:
                self._reap_idle_streams_locked()
                self._stream_counter += 1
                sid = str(self._stream_counter)
                self._streams[sid] = _StreamPump(
                    gen, multiplexed_model_id, self._chunks_ready,
                    on_cancel=on_cancel, on_delivered=on_delivered,
                )
            envelope = {
                "__serve_stream__": sid,
                "content_type": ctype,
                "status": status,
                "headers": extra,
            }
            if resume is not None:
                # Migration descriptor rides the envelope: the proxy uses
                # it to resubmit this request elsewhere if THIS replica
                # dies mid-stream. The deployment supplies kind + body; the
                # ORIGINAL routing context (method/path/headers/model id/
                # mount) is stamped here so the resumed request dispatches
                # identically — a multiplexed or sub-routed deployment must
                # not resume under different semantics.
                envelope["__serve_resume__"] = dict(
                    resume,
                    ctx={
                        "method": method,
                        "path": path,
                        "query": query,
                        "headers": headers,
                        "model_id": multiplexed_model_id,
                        "route_prefix": route_prefix,
                        "raw_query": raw_query_string,
                    },
                )
            return envelope
        return result

    def _reap_idle_streams_locked(self):
        """Backstop for proxies that died mid-stream (normal disconnects
        send cancel_stream): cancel pumps nobody drained for 5 minutes so
        generator finalizers run and state doesn't accumulate."""
        now = time.time()
        for sid, pump in list(self._streams.items()):
            if now - pump.last_pump > 300.0:
                self._streams.pop(sid, None)
                pump.cancel()

    def next_stream_chunks(self, poll):
        """One proxy's poll for ALL the streams it can take chunks for: wait
        (at most ``_POLL_WAIT_S``) until ANY of them has something, then sweep
        every one that has. What is ready leaves: no timer, no wait for a
        pass's end, no least batch. Returns ``{sid: batch}`` with a batch
        ``{"chunks": [bytes], "done": bool}`` for each stream that had
        something, ``None`` for a stream this replica does not know (gone:
        finished, cancelled or reaped), and nothing for a stream that had
        nothing yet: poll again. A stream whose producer raised gets the
        chunks it yielded before, and on its next poll ``{"chunks": [],
        "done": False, "error": TaskError}``: that stream's alone, the others'
        batches leave beside it.

        ``poll`` is ONE argument, ``(poller, n, t_asked_ns, [(sid, t_got_ns,
        wrote_ns), ...])``, because every argument of an actor call is
        serialized by itself, in the proxy, whose polls decide the streams'
        gaps (a second argument cost 32 streams 2.3 ms of their p95 gap:
        PERF.md, PR 38). ``poller`` and ``n`` name the proxy and number its
        polls, for ``wake_stream_poll``. The rest are the proxy's of
        ``CHUNK_STAMPS``: when it handed THIS poll to its executor, and for
        each stream when the batch its last poll returned reached the proxy's
        loop and each of its chunks was written (0 and () where there was
        none)."""
        t_enter_ns = time.monotonic_ns()
        poller, n, t_asked_ns, asked = poll
        reply: dict = {}
        pumps: dict = {}
        with self._lock:
            now = time.time()
            for sid, _, _ in asked:
                pump = self._streams.get(sid)
                if pump is None:
                    reply[sid] = None
                else:
                    pump.last_pump = now  # the idle reaper's, for every stream a poll names
                    pumps[sid] = pump
        for sid, t_got_ns, wrote_ns in asked:
            if sid in pumps:
                pumps[sid].delivered(t_got_ns, wrote_ns)
        if not reply:  # a stream that is gone is news already
            deadline = time.monotonic() + _POLL_WAIT_S
            with self._chunks_ready:
                while not (
                    any(pump.q.qsize() for pump in pumps.values())
                    or (n and self._poll_kicks.get(poller, 0) >= n)
                ):
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._chunks_ready.wait(left)
        chunks = streams = 0
        with _annotation("serve.stream.sweep"):
            for sid, pump in pumps.items():
                if not pump.q.qsize():
                    continue
                batch, yields = self._sweep_stream(sid, pump)
                pump.swept(yields, t_asked_ns, t_enter_ns)
                if batch["done"] or "error" in batch:
                    pump.delivered()  # no next poll will bring the proxy's stamps
                reply[sid] = batch
                chunks += len(yields)
                streams += bool(yields)
        if chunks:
            with self._lock:
                self._stream_polls += 1
                self._stream_poll_chunks += chunks
                self._stream_poll_streams += streams
        return reply

    def next_stream_chunk(self, sid):
        """The one-element case of ``next_stream_chunks``, for a caller that
        holds one stream: block briefly for the first chunk (one-item latency
        for time-to-first-byte), then sweep whatever else is already buffered
        into the same response. Returns {"chunks": [bytes], "done": bool} —
        empty chunks + done=False means "nothing yet, poll again" — or None
        for unknown streams; raises what the stream's producer raised.

        ``sid`` is the stream's id, or the id with the proxy's of
        ``CHUNK_STAMPS`` behind it, ``(sid, t_asked_ns, t_got_ns, wrote_ns)``.
        A caller that passes the bare id gets the same batches."""
        t_asked_ns, t_got_ns, wrote_ns = 0, 0, ()
        if isinstance(sid, tuple):
            sid, t_asked_ns, t_got_ns, wrote_ns = sid
        reply = self.next_stream_chunks(("", 0, t_asked_ns, [(sid, t_got_ns, wrote_ns)]))
        batch = reply.get(sid, {"chunks": [], "done": False})
        if batch and "error" in batch:
            raise batch["error"].cause
        return batch

    def wake_stream_poll(self, poll):
        """``(poller, n)``: that proxy's poll ``n`` (and any before it) returns
        at once with what it has, also if it has not arrived yet. A proxy
        sends it when a stream starts to wait that its poll in flight does not
        name (a stream just opened, a slow client that caught up), so that the
        stream is in the next poll now and not after ``_POLL_WAIT_S``."""
        poller, n = poll
        with self._chunks_ready:
            if n > self._poll_kicks.get(poller, 0):
                self._poll_kicks[poller] = n
            self._chunks_ready.notify_all()
        return True

    def _sweep_stream(self, sid: str, pump: _StreamPump):
        """What the stream's queue holds now, without waiting: (the batch,
        the ``t_yield_ns`` of each of its chunks)."""
        chunks: list[bytes] = []
        yields: list[int] = []
        done = False
        error = None
        while True:
            try:
                kind, payload, t_yield_ns = pump.q.get_nowait()
            except _queue.Empty:
                break
            if kind == "chunk":
                chunks.append(payload)
                yields.append(t_yield_ns)
            elif kind == "done":
                done = True
                break
            else:  # error
                error = payload
                break
        if error is not None and chunks:
            # Deliver what the producer yielded BEFORE it raised; the error
            # surfaces on the next poll (parity with the old per-item pump).
            pump._put(("error", error, 0))
            return {"chunks": chunks, "done": False}, yields
        if done or error is not None:
            with self._lock:
                self._streams.pop(sid, None)
        if error is not None:
            return {"chunks": [], "done": False, "error": _shippable(error)}, yields
        return {"chunks": chunks, "done": done}, yields

    def cancel_stream(self, sid: str):
        """Proxy-initiated teardown on client disconnect (reference: ASGI
        disconnect -> request cancellation): stop the pump thread now
        instead of waiting out the idle reaper."""
        with self._lock:
            pump = self._streams.pop(sid, None)
        if pump is not None:
            pump.cancel()
        return True

    def get_metrics(self) -> dict:
        """Queue stats for autoscaling (reference: autoscaling_metrics.py)."""
        with self._lock:
            return {
                "ongoing": self._ongoing,
                "total": self._total,
                "ts": time.time(),
                # Polls that carried a chunk, the chunks and the streams with
                # a chunk in them: chunks / polls is what one call moved.
                "stream_polls": self._stream_polls,
                "stream_poll_chunks": self._stream_poll_chunks,
                "stream_poll_streams": self._stream_poll_streams,
            }

    def drain(self) -> bool:
        """Enter drain mode (controller-initiated, deliberate retirement):
        refuse NEW requests with the typed ReplicaDrainingError while
        in-flight requests and live stream pumps run to completion. The
        user callable's own drain() hook (e.g. the LLM engine's
        refuse-admissions flag) is forwarded to."""
        with self._lock:
            self._draining = True
        fn = getattr(self._callable, "drain", None)
        if fn is not None and callable(fn):
            try:
                fn()
            except Exception:
                pass
        return True

    # While draining, a pump nobody polled for this long stops COUNTING
    # toward drain completion: its proxy probably died without
    # cancel_stream (a live proxy polls sub-second), and the normal 300s
    # idle reaper only runs from handle_http_request, which the drain gate
    # refuses — without this, one orphan pump rides out the whole
    # drain_timeout_s on an otherwise idle replica. The pump is NOT
    # cancelled here: a slow-but-alive consumer (proxy blocked in a big
    # send) must not be silently truncated as "complete" — if it is still
    # alive at retire, its next poll gets the typed went-away error and
    # resumable streams migrate.
    _DRAIN_IDLE_EXCLUDE_S = 10.0

    def drain_status(self) -> dict:
        """What the controller's drainer polls: retire once ongoing == 0
        and no RECENTLY-PUMPED stream remains (or drain_timeout_s
        expires)."""
        with self._lock:
            now = time.time()
            streams = (
                sum(
                    1
                    for pump in self._streams.values()
                    if now - pump.last_pump <= self._DRAIN_IDLE_EXCLUDE_S
                )
                if self._draining
                else len(self._streams)
            )
            return {
                "draining": self._draining,
                "ongoing": self._ongoing,
                "streams": streams,
            }

    def check_health(self) -> bool:
        fn = getattr(self._callable, "check_health", None)
        if fn is not None:
            fn()
        if not SETUP_STAMPS["t_ready_ns"]:
            SETUP_STAMPS["t_ready_ns"] = time.monotonic_ns()
            since = SETUP_STAMPS["t_requested_ns"] or SETUP_STAMPS["t_actor_ns"]
            logger.info("setup: ready %.1f s after the controller asked", (SETUP_STAMPS["t_ready_ns"] - since) / 1e9)
        return True

    def prepare_for_shutdown(self):
        """Invoke the user callable's shutdown hook, if any (reference:
        replica graceful_shutdown path)."""
        stop = getattr(self, "_metrics_stop", None)
        if stop is not None:
            stop.set()  # retired replicas must not keep pushing metrics
        fn = getattr(self._callable, "prepare_for_shutdown", None) or getattr(
            self._callable, "shutdown", None
        )
        if fn is not None and callable(fn):
            fn()
        return True


class HTTPRequest:
    """Minimal request object handed to deployments from the proxy
    (stands in for the reference's starlette.requests.Request)."""

    def __init__(self, method: str, path: str, query: dict, body: bytes, headers: dict,
                 route_prefix: str | None = None, raw_query_string: str | None = None):
        self.method = method
        self.path = path
        self.query_params = query
        self.body = body
        self.headers = headers
        self.route_prefix = route_prefix
        # Wire-exact query string (duplicate keys/order intact) for ASGI
        # ingress; query_params remains the collapsed dict convenience.
        self.raw_query_string = raw_query_string

    @property
    def sub_path(self) -> str:
        """Path RELATIVE to the deployment's matched route prefix — what
        sub-route dispatch (DAGDriver) should match on, valid under any
        mount point."""
        if not self.route_prefix or self.route_prefix == "/":
            return self.path
        rest = self.path[len(self.route_prefix.rstrip("/")):]
        return rest if rest.startswith("/") else "/" + rest if rest else "/"

    def json(self):
        import json as _json

        return _json.loads(self.body or b"null")

    def text(self) -> str:
        return (self.body or b"").decode()


def _shippable(error: BaseException):
    """A producer's exception as the ``TaskError`` a raising actor call rides
    home in, so that the stream's owner in the proxy sees what it always saw;
    one that does not pickle goes as its ``repr``, and cannot take the other
    streams' batches of the same reply down with it."""
    from ray_tpu._private import serialization
    from ray_tpu.exceptions import TaskError

    text = "".join(traceback.format_exception(type(error), error, error.__traceback__))
    err = TaskError(cause=error, remote_traceback=text, task_name="next_stream_chunks")
    try:
        serialization.serialize(err)
    except Exception:
        err = TaskError(cause=RuntimeError(repr(error)), remote_traceback=text, task_name="next_stream_chunks")
    return err


def _encode_chunk(item) -> bytes:
    if isinstance(item, bytes):
        return item
    if isinstance(item, str):
        return item.encode()
    import json as _json

    return (_json.dumps(item) + "\n").encode()

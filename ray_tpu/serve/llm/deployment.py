"""Serve-ready wrapper around ``LLMEngine``: streaming chat behind HTTP.

Deploy it like any callable — the replica holds the engine (params + the
two compiled paged-cache programs), requests stream tokens over SSE through
the existing replica ``_StreamPump`` path, and a client disconnect frees the
request's decode slot and KV blocks immediately via
``StreamingResponse.on_disconnect``:

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    app = serve.deployment(num_replicas=2)(LLMDeployment).bind(
        model_config={"vocab_size": 512, "d_model": 128, ...},
        engine_config={"num_slots": 8, "block_size": 16},
    )
    serve.run(app, route_prefix="/llm")

    curl -N http://host:port/llm -d '{"tokens": [1,2,3], "max_new_tokens": 16}'
    data: {"token": 42}
    ...
    data: [DONE]

Request body: ``{"tokens": [int], "max_new_tokens": int, "temperature":
float, "top_k": int, "seed": int, "stream": bool}`` — ``stream`` defaults
true (SSE); false buffers and returns ``{"tokens": [...]}``.

Disaggregated serving (ISSUE 20): give the engine ``role="prefill"`` and
requests terminate with a ``{"__llm_handoff__": ...}`` envelope — the
sealed-KV descriptor plus the first sampled token — instead of decoding.
The proxy forwards that descriptor to a decode-pool replica as
``kv_import=`` + ``resume_tokens=`` (+ ``echo_resume``, so the client
still sees the prefill-sampled token in its stream). Build the two-pool
app with :func:`disaggregated_llm_app`.
"""

from __future__ import annotations

import collections
import json

from ray_tpu.serve._private.common import (  # noqa: F401
    PREFILL_SUFFIX,
    RECV_STAMP_HEADER,
    REQUEST_ID_HEADER,
)
from ray_tpu.serve.llm.engine import LLMEngine, prefix_route_hint  # noqa: F401


class LLMDeployment:
    @staticmethod
    def serve_concurrency(model_config=None, engine_config=None, **_) -> int:
        """Queries at once that a replica built from these arguments has rows for (``Deployment.bind`` reads it)."""
        return int((engine_config or {}).get("num_slots", 0))

    def __init__(
        self,
        model_config: dict,
        engine_config: dict | None = None,
        init_seed: int = 0,
        params=None,
    ):
        # The stages of the replica's start that come before the engine's own
        # (get_stats()["spans"]["stages"], and "setup" computed from them).
        from ray_tpu.serve.llm.stats import listen_for_compiles, listen_for_gc, stage

        stages: list = []
        with stage(stages, "jax_import"):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.transformer import TransformerConfig, init_params

            listen_for_compiles()  # before the first program: the draw's compiles count
            listen_for_gc()  # and its collections
        with stage(stages, "backend"):
            jax.devices()
        model_config = dict(model_config)
        for key in ("dtype", "param_dtype"):
            if isinstance(model_config.get(key), str):  # JSON-friendly configs
                model_config[key] = jnp.dtype(model_config[key]).type
        self.cfg = TransformerConfig(**model_config)
        if params is None:
            with stage(stages, "params"):
                params = jax.block_until_ready(
                    init_params(jax.random.PRNGKey(init_seed), self.cfg)
                )
        self.engine = LLMEngine(params, self.cfg, **(engine_config or {}))
        self.engine.spans.stages[:0] = stages

    def __call__(self, request):
        from ray_tpu.serve.api import StreamingResponse

        body = request.json() if hasattr(request, "json") else dict(request)
        req = self.engine.submit(
            body["tokens"],
            max_new_tokens=int(body.get("max_new_tokens", 32)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            seed=int(body.get("seed", 0)),
            resume_tokens=body.get("resume_tokens"),
            kv_import=body.get("kv_import"),
            **_proxy_stamps(getattr(request, "headers", None)),
        )
        # Resume tokens a migrated/handed-off request already owns but the
        # CLIENT has not seen yet (the handoff descriptor's first sampled
        # token): echo them ahead of the engine's stream so the client's
        # token sequence is complete. The engine itself never re-emits
        # resume tokens — echoing is presentation, owned here.
        echo = [int(t) for t in (body.get("resume_tokens") or ())] if body.get(
            "echo_resume"
        ) else []
        if self.engine.role == "prefill":
            return self._prefill_call(body, req)
        if not body.get("stream", True):
            try:
                toks = req.result(timeout=float(body.get("timeout", 120.0)))
                return {"tokens": echo + toks}
            except BaseException:
                # A timed-out (or otherwise failed) buffered request must not
                # keep generating into a queue nobody will read — free its
                # decode slot and KV blocks now, like the SSE path does.
                self.engine.cancel(req)
                raise
        engine = self.engine
        annotation = engine.spans.annotation
        # A token's way back (stats.DELIVERY_FIELDS): the pump thread notes
        # each event's ``t_emit_ns`` as it yields it (0: an event that is no
        # token of this engine's), the replica hands back the events' stamps
        # batch by batch and in the same order, and the two are one record.
        emitted: collections.deque = collections.deque()
        number = int(req.id.rpartition("-")[2])
        n_recorded = 0

        def sse():
            try:
                for tok in echo:
                    emitted.append(0)
                    yield f"data: {json.dumps({'token': tok})}\n\n"
                for tok, t_emit_ns in req.stamped():
                    # Around building the event and, through the yield, the
                    # pump's queueing of it.
                    with annotation("llm.sse.event"):
                        emitted.append(t_emit_ns)
                        yield f"data: {json.dumps({'token': tok})}\n\n"
                emitted.append(0)
                yield "data: [DONE]\n\n"
            finally:
                # Belt: normal completion makes this a no-op; an aborted
                # generator (pump saw `cancelled` at a yield) frees the
                # request even if on_disconnect never fired.
                engine.cancel(req)

        def delivered(batch: list):
            nonlocal n_recorded
            recs = []
            for stamps in batch:
                t_emit_ns = emitted.popleft()
                if t_emit_ns:
                    recs.append((number, n_recorded, t_emit_ns) + stamps)
                    n_recorded += 1
            engine.spans.deliveries.push(recs)

        return StreamingResponse(
            sse(),
            content_type="text/event-stream",
            # Suspenders: fires synchronously from cancel_stream / the idle
            # reaper, so the decode slot and KV blocks free immediately
            # even while the generator is parked waiting for a token.
            on_disconnect=lambda: engine.cancel(req),
            on_delivered=delivered,
            # Migration descriptor: if THIS replica dies mid-stream, the
            # proxy resubmits the original body to another replica with
            # resume_tokens= the tokens it already forwarded; "sse_tokens"
            # tells the proxy how to parse them back out of the SSE chunks
            # it relayed. The one-shot handoff fields must NOT ride along:
            # kv_import's payload is gone after the first import, and a
            # re-echo would duplicate tokens the client already has.
            # Counter-based sampling makes the continuation bit-identical,
            # so the client never notices.
            resume={
                "kind": "sse_tokens",
                "body": {
                    k: v
                    for k, v in body.items()
                    if k not in ("resume_tokens", "kv_import", "echo_resume")
                },
            },
        )

    def _prefill_call(self, body: dict, req) -> dict:
        """Prefill-role request: block until the engine finishes prefill and
        return the handoff envelope the proxy forwards to the decode pool.
        When the engine could not seal a payload (bare process) it decoded
        locally instead — return the plain buffered result so a mono-pool
        fallback still answers the client."""
        try:
            toks = req.result(timeout=float(body.get("timeout", 120.0)))
        except BaseException:
            self.engine.cancel(req)
            raise
        if req.handoff is None:
            return {"tokens": toks}
        desc = dict(req.handoff)
        tok0 = desc.pop("tok0")
        return {
            "__llm_handoff__": {
                "kv_import": desc,
                "resume_tokens": [tok0],
                "body": {
                    k: v
                    for k, v in body.items()
                    if k not in ("resume_tokens", "kv_import", "echo_resume")
                },
            }
        }

    def get_stats(self) -> dict:
        """Engine snapshot plus the device this replica runs on and, under
        ``"spans"``, the engine's iteration, request, compile, delivery and
        collector records and the records of the replica's start, its own
        stamps (``replica.SETUP_STAMPS``) among them
        (``stats.EngineSpans.export``) (handle-callable; used by tests and
        benches)."""
        from ray_tpu.util.device_report import device_report

        from ray_tpu.serve._private.replica import SETUP_STAMPS
        from ray_tpu.serve.llm.stats import FOREIGN_STAMP_S

        ring = self.engine.spans.deliveries
        stamps = dict(SETUP_STAMPS)
        if abs(stamps["t_requested_ns"] - stamps["t_actor_ns"]) > FOREIGN_STAMP_S * 1e9:
            stamps["t_requested_ns"] = 0  # a controller on another host: not this clock
        return {
            **self.engine.stats(),
            # The polls that carried a token of this engine's, and the tokens
            # and streams they carried (what ``on_delivered`` was handed: a new
            # ``t_enter_ns`` is a new poll): chunks / polls is the tokens a call
            # of the proxy's moved, 1 with a poll a stream and token.
            "stream_polls": ring.polls,
            "stream_poll_chunks": ring.n,
            "stream_poll_streams": ring.batches,
            "device": device_report(),
            "spans": {**self.engine.spans.export(), "setup_stamps": stamps},
        }

    def check_health(self):
        self.engine.check_health()

    def drain(self):
        """Controller-initiated drain-before-retire: the engine refuses new
        admissions; in-flight decodes run to completion."""
        self.engine.drain()

    def prepare_for_shutdown(self):
        self.engine.shutdown()


def _proxy_stamps(headers) -> dict:
    """What the proxy put into the headers it forwards: its identifier of
    the request and its CLOCK_MONOTONIC stamp of receiving it."""
    if not headers:
        return {}
    stamp = headers.get(RECV_STAMP_HEADER, "")
    return {
        "request_id": headers.get(REQUEST_ID_HEADER, ""),
        "t_recv_ns": int(stamp) if stamp.isdigit() else 0,
    }


def disaggregated_llm_app(
    model_config: dict,
    engine_config: dict | None = None,
    *,
    name: str = "llm",
    prefill_replicas: int = 1,
    decode_replicas: int = 1,
    cluster_prefix: bool = True,
    max_concurrent_queries: int = 100,
    init_seed: int = 0,
    route_prefix: str | None = "/llm",
):
    """Build the two-pool disaggregated serving application: a decode
    deployment that OWNS the route and a paired ``<name>--prefill``
    deployment the proxy discovers by naming convention. Pool sizes are
    static config (no cross-pool autoscaler yet — see PARITY.md). Returns
    the decode Application; ``serve.run(app)`` deploys both pools.
    """
    from ray_tpu import serve

    engine_config = dict(engine_config or {})
    engine_config.pop("role", None)
    prefill_cfg = dict(
        engine_config, role="prefill", cluster_prefix=cluster_prefix
    )
    decode_cfg = dict(engine_config, role="decode", cluster_prefix=False)
    prefill = serve.deployment(
        num_replicas=int(prefill_replicas),
        name=f"{name}{PREFILL_SUFFIX}",
        max_concurrent_queries=max_concurrent_queries,
        route_prefix=None,
    )(LLMDeployment).bind(
        model_config=model_config,
        engine_config=prefill_cfg,
        init_seed=init_seed,
    )
    decode = serve.deployment(
        num_replicas=int(decode_replicas),
        name=name,
        max_concurrent_queries=max_concurrent_queries,
        route_prefix=route_prefix,
    )(LLMDeployment).bind(
        model_config=model_config,
        engine_config=decode_cfg,
        init_seed=init_seed,
    )
    # The decode app is the root; the prefill app rides as a sibling of
    # the same application tree (deployed together, torn down together).
    decode.extras.append(prefill)
    return decode

"""Serve a jit-compiled LM with KV-cache decode behind HTTP.

Two flavors:

- /generate — the simple one-batch path: POST {"tokens": [...]}, buffered
  JSON reply; batched handle calls share the one compiled prefill/decode.
- /chat — continuous batching (serve.llm.LLMDeployment): paged KV cache,
  slot-level admission mid-decode, prefix-cache reuse for shared system
  prompts, per-token SSE streaming; requests carrying the system prompt's
  `serve_prefix_hash` header route to the replica holding its KV blocks.

On TPU the replica pins a chip:
@serve.deployment(ray_actor_options={"num_tpus": 1}).

Run: python examples/serve_llm.py
"""

import json
import urllib.request


def main():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=2)
    serve.start()

    @serve.deployment
    class LM:
        def __init__(self):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.transformer import TransformerConfig, init_params

            self.cfg = TransformerConfig(
                vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
                d_ff=128, max_seq_len=64, dtype=jnp.float32, remat=False,
            )
            self.params = init_params(jax.random.PRNGKey(0), self.cfg)

        def __call__(self, request):
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models.generate import generate

            body = request.json()
            out = generate(
                self.params,
                jnp.asarray([body["tokens"]], jnp.int32),
                self.cfg,
                max_new_tokens=int(body.get("max_new_tokens", 8)),
                temperature=float(body.get("temperature", 0.0)),
            )
            return {"tokens": np.asarray(out)[0].tolist()}

    serve.run(LM.bind(), route_prefix="/generate")
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}/generate",
        data=json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 6}).encode(),
    )
    print("generated:", json.loads(urllib.request.urlopen(req, timeout=60).read()))

    # --- continuous batching + SSE streaming (serve.llm) ---
    from ray_tpu.serve.llm import LLMDeployment, prefix_route_hint

    chat = serve.deployment(name="Chat")(LLMDeployment).bind(
        dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
             d_ff=128, max_seq_len=64, dtype="float32", remat=False),
        engine_config=dict(num_slots=4, block_size=8, max_model_len=64,
                           prefill_chunk=8),
    )
    serve.run(chat, route_prefix="/chat")
    system = list(range(1, 9))  # one full shared block
    req = urllib.request.Request(
        f"http://{host}:{port}/chat",
        data=json.dumps({"tokens": system + [42], "max_new_tokens": 8}).encode(),
        headers={"serve_prefix_hash": prefix_route_hint(system, 8)},
    )
    resp = urllib.request.urlopen(req, timeout=120)
    toks = []
    for event in resp.read().split(b"\n\n"):
        if event.startswith(b"data: ") and event != b"data: [DONE]":
            toks.append(json.loads(event[6:])["token"])
    print("streamed:", toks)
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()

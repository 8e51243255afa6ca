"""``LLMDeployment`` with the handle-callable methods the benchmark needs.

Only the process that holds the chip can trace it, check it against the
reference or time the engine without Serve in the way, so these run inside the
replica. Nothing of ``LLMDeployment`` is overridden: requests take the same
path as in any deployment.
"""

from __future__ import annotations

import time

from ray_tpu.serve.llm.deployment import LLMDeployment


class BenchLLMDeployment(LLMDeployment):
    def device(self) -> dict:
        """The device as jax reports it here, with the peak on the fullest chip."""
        from benchmarks.harness.device import device_line

        return device_line()

    # -- profiler: a slice in the middle of the window ---------------------

    def trace_start(self, log_dir: str) -> float:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's Python frames would swamp the file
        options.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=options)
        self._trace_dir, self._trace_t0 = log_dir, time.monotonic()
        return self._trace_t0

    def trace_stop(self) -> float:
        import jax

        slice_s = time.monotonic() - self._trace_t0
        jax.profiler.stop_trace()
        return slice_s

    def trace_reduce(self, programs: dict) -> dict:
        """After the window: the slice reduced to what the metric readers use."""
        from benchmarks.harness import trace

        return trace.reduce_file(self._trace_dir, programs)

    # -- Serve's share of the time to a first token -------------------------

    def engine_probe(self, tokens: list, max_new_tokens: int) -> float:
        """Milliseconds from ``engine.submit`` to the first token, in here."""
        t0 = time.monotonic()
        req = self.engine.submit(tokens, max_new_tokens=max_new_tokens)
        first = None
        for _ in req:
            if first is None:
                first = time.monotonic()
        return (first - t0) * 1000.0

    # -- correctness ---------------------------------------------------------

    def reference_check(self, model: dict, sequences: list, n_prompt: list) -> list:
        """For each sequence (prompt + the tokens the system returned, greedy):
        at every generated position, how far the reference's logit of the
        system's token lies under the reference's largest logit."""
        import numpy as np

        from benchmarks.harness import reference

        logits_fn = reference.make_layerwise_logits(model)
        pad_to = max(len(s) for s in sequences)
        out = []
        for seq, n in zip(sequences, n_prompt):
            rows = list(range(n - 1, len(seq) - 1))  # position that predicts each new token
            padded = list(seq) + [0] * (pad_to - len(seq))  # causal: the tail changes nothing before it
            logits = np.asarray(logits_fn(self.engine.params, padded, rows))
            chosen = logits[np.arange(len(rows)), np.asarray(seq[n:])]
            out.append(
                {
                    "max_gap": float(np.max(logits.max(axis=-1) - chosen)),
                    "finite": bool(np.isfinite(logits).all()),
                    "logit_std": float(logits.std()),
                }
            )
        return out

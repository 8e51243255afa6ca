"""The accelerator path reads no environment variable.

What the benchmark's cells run (the engine's decode and prefill-chunk programs,
the train step) is traced from ``models/``, ``ops/``, ``parallel/``,
``serve/llm/`` and ``train/jax/``. A variable read there at trace time is a
second path that no cell, test or ledger line ever sees: decide an A/B on the
chip and delete the arm that lost, or make the choice an argument a caller
passes. Process-level settings (``JAX_PLATFORMS``, the compile cache) belong
to the entry points and ``ray_tpu/util/``.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGES = ("models", "ops", "parallel", "serve/llm", "train/jax")
READS = re.compile(r"\bos\.environ\b|\bgetenv\b|\bfrom os import\b.*\benviron\b")


@pytest.mark.parametrize("package", PACKAGES)
def test_package_reads_no_environment_variable(package):
    root = os.path.join(REPO, "ray_tpu", package)
    sources = [
        os.path.join(d, f) for d, _, files in os.walk(root) for f in files if f.endswith(".py")
    ]
    assert sources, f"no sources under {root}"
    hits = []
    for path in sources:
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if READS.search(line):
                    hits.append(f"{os.path.relpath(path, REPO)}:{n}: {line.strip()}")
    assert not hits, "environment reads on the accelerator path:\n" + "\n".join(hits)

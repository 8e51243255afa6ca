"""The toy sizes the benchmark's tests run at. They live here, never in
``benchmarks/configs/``: a configuration file holds published widths only."""

import copy
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import registry  # noqa: E402


def toy_cell(manifest: dict, workload: str) -> dict:
    """The cell with every width shrunk to what a CPU runs in seconds."""
    cell = copy.deepcopy(registry.load_cell(manifest, workload))
    cfg = cell["config"]
    cfg.update(
        hidden_size=128, intermediate_size=256, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=512, sliding_window=64,
    )
    mix = cell["traffic"]
    mix["trace_slice_s"] = 0.5
    if cfg["path"] == "serve":
        cfg["deployment"]["engine"] = dict(num_slots=4, block_size=16, max_model_len=256, num_blocks=65)
        cfg["check"].update(prompt_lens=[40, 64], new_tokens=4, probe_len=16, probe_pairs=2)
        mix["preroll_s"] = 0.5
        for key, small in (("prompt_len", (8, 100, 30)), ("output_len", (4, 40, 12))):
            lo, hi, median = small
            mix[key].update(min=lo, max=hi)
            if mix[key]["dist"] == "lognormal":
                mix[key]["median"] = median
        if mix["arrival"]["process"] == "closed":
            mix["arrival"]["clients"] = 8
        else:
            mix["arrival"]["rate_per_s"] = 4.0
    else:
        mix["seq_len"] = 128
    return cell

"""Tokens of the steps completed in the window, all chips together, over the
time from its opening to the host's fetch of the last step's loss."""


def read(result):
    t = result["train"]
    return t["steps"] * t["tokens_per_step"] / t["window_s"]

"""The traffic generator and the client's arithmetic."""

import json
import os

import pytest

from benchmarks.harness import client, registry, traffic

MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(registry.BENCH_DIR, "traffic")) if f.endswith(".json")
)


def _mix(name):
    with open(os.path.join(registry.BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


SERVE_MIXES = [m for m in MIXES if _mix(m)["kind"] == "serve"]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_the_same_seed_gives_the_same_schedule(name):
    a = traffic.schedule(_mix(name), 2**31 + 5, 20, 32000)
    b = traffic.schedule(_mix(name), 2**31 + 5, 20, 32000)
    assert a == b
    assert a != traffic.schedule(_mix(name), 6, 20, 32000)


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_two_seeds_offer_the_same_token_load(name):
    mix = _mix(name)
    plans = [traffic.schedule(mix, seed, 20, 32000) for seed in (1, 2, 2**31 + 99)]
    assert len({traffic.offered_tokens(p) for p in plans}) == 1

    def lengths(plan):
        reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
        return sorted(len(r["tokens"]) for r in reqs), sorted(r["max_new_tokens"] for r in reqs)

    assert lengths(plans[0]) == lengths(plans[1]) == lengths(plans[2])
    for plan in plans:
        reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
        for r in reqs:
            assert mix["prompt_len"]["min"] <= len(r["tokens"]) <= mix["prompt_len"]["max"]
            assert all(0 <= t < 32000 for t in r["tokens"])
            assert len(r["tokens"]) + r["max_new_tokens"] <= 2560


def test_open_loop_arrivals_fill_the_span_at_the_rate_for_every_seed():
    mix = dict(_mix("chat-open"), preroll_s=5.0)
    mix.pop("schedule_seed", None)
    mix["arrival"] = {"process": "poisson", "rate_per_s": 3.0}
    for seed in (1, 2):
        dues = sorted(r["due_s"] for r in traffic.schedule(mix, seed, 40, 32000)["open"])
        assert len(dues) == 135  # 3 a second over 5 + 40 seconds
        assert -5.0 < dues[0] and dues[-1] < 40.0
    a, b = (sorted(r["due_s"] for r in traffic.schedule(mix, s, 40, 32000)["open"]) for s in (1, 2))
    assert a != b  # another order of the same gaps


def test_a_pinned_schedule_keeps_what_meets_what_and_the_seed_changes_the_rest():
    mix = dict(_mix("chat-open"), schedule_seed=23)
    a, b = (traffic.schedule(mix, seed, 40, 32000)["open"] for seed in (1, 2**31 + 2))
    shape = lambda reqs: [(r["due_s"], len(r["tokens"]), r["max_new_tokens"], r["temperature"]) for r in reqs]  # noqa: E731
    assert shape(a) == shape(b)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert [r["seed"] for r in a] != [r["seed"] for r in b]


def test_gamma_arrivals_are_burstier_than_poisson_at_the_same_rate():
    import random
    import statistics

    def cv(arrival):
        t = traffic.arrival_times(arrival, 200.0, random.Random(3), True)
        gaps = [b - a for a, b in zip([0.0] + t, t)]
        return statistics.pstdev(gaps) / statistics.mean(gaps), len(t)

    cv_p, n_p = cv({"process": "poisson", "rate_per_s": 2.0})
    cv_g, n_g = cv({"process": "gamma", "rate_per_s": 2.0, "cv": 3.0})
    assert n_p == n_g == 400
    assert 0.9 < cv_p < 1.1 and 2.3 < cv_g < 3.3


def test_closed_loop_shared_prefix_sessions_and_sampling_are_all_data():
    mix = {
        "arrival": {"process": "closed", "clients": 4, "requests_per_client": 5},
        "prompt_len": {"dist": "mixture", "values": [600, 700], "weights": [1, 1]},
        "output_len": {"dist": "uniform", "min": 4, "max": 8},
        "shared_prefix": {"groups": 2, "prefix_len": 512, "share": 1.0},
        "sessions": {"turns": 3, "think_time_s": 2.0, "growth": {"dist": "uniform", "min": 100, "max": 200}},
        "sampling": {"sampled_share": 0.5, "temperature": 0.7, "top_k": 50},
    }
    plan = traffic.schedule(mix, 7, 10, 1000)
    assert len(plan["closed"]) == 4 and all(len(c) == 5 for c in plan["closed"])
    reqs = [r for c in plan["closed"] for r in c]
    assert len({tuple(r["tokens"][:512]) for r in reqs}) == 2  # two shared prefixes
    assert sum(r["temperature"] > 0 for r in reqs) == 10
    assert all(r["top_k"] == (50 if r["temperature"] else 0) for r in reqs)
    assert all(len(r["followups"]) == 2 for r in reqs)
    assert all(100 <= len(f["new_tokens"]) <= 200 for r in reqs for f in r["followups"])


@pytest.mark.parametrize(
    "values, q, want",
    [([1, 2, 3, 4], 50, 2.5), ([5], 90, 5.0), (list(range(1, 102)), 90, 91.0),
     ([1.0, 2.0, float("inf")], 50, 2.0), ([1.0, float("inf")], 90, float("inf"))],
)
def test_percentile_is_numpys_default_and_carries_requests_that_never_answered(values, q, want):
    assert client.percentile(values, q) == want


def _sample(due, sent, times, tokens, want, done=True, error=None):
    s = client._Sample(due, want)
    s.sent, s.times, s.tokens, s.done, s.error = sent, list(times), list(tokens), done, error
    return s


def test_open_loop_latency_counts_from_the_due_time_and_lateness_is_reported():
    t_open = 100.0
    samples = [
        # due at 101, sent 30 ms late, first token at 101.5: 500 ms, not 470.
        _sample(101.0, 101.03, [101.5, 101.6, 101.7], [1, 2, 3], 3),
        # pre-roll: not a request of the window, but its tokens in the window count.
        _sample(99.0, 99.0, [99.5, 100.2, 100.4], [1, 2, 3], 3),
        # failed: counts as never.
        _sample(102.0, 102.0, [], [], 4, done=False, error="HTTP 500"),
        # cut at the end of the window while streaming: healthy.
        _sample(109.0, 109.0, [109.5, 110.5], [4, 5], 8, done=False),
        # a token outside the vocabulary.
        _sample(103.0, 103.0, [103.1], [99999], 1),
    ]
    out = client.summarise(samples, t_open, 10.0, vocab_size=1000)
    assert out["attempted"] == 4 and out["failed"] == 2 and out["finished"] == 2
    assert out["ttft_ms"][0] == pytest.approx(500.0)
    assert sorted(out["ttft_ms"])[-2:] == [float("inf")] * 2
    assert out["tokens_in_window"] == 3 + 2 + 1 + 1
    assert sorted(round(g) for g in out["itl_ms"]) == [100, 100, 200, 700]
    assert out["generator_late_ms"]["max"] == pytest.approx(30.0)
    assert out["errors"] == ["HTTP 500"]


def test_closed_loop_requests_count_if_they_streamed_inside_the_window():
    samples = [
        _sample(None, 5.0, [5.2, 5.3], [1, 2], 2),
        _sample(None, 0.5, [0.7, 1.5], [1, 2], 2),  # sent in the pre-roll, still streaming
        _sample(None, 0.2, [0.4, 0.6], [1, 2], 2),  # over before the window opened
        _sample(None, 9.0, [], [], 4, done=False),  # still in the server's queue at the end
        _sample(None, 9.5, [], [], 4, done=False, error="HTTP 503"),
    ]
    out = client.summarise(samples, 1.0, 10.0, vocab_size=10)
    assert out["attempted"] == 3 and out["failed"] == 1
    assert out["ttft_ms"][:2] == [pytest.approx(200.0)] * 2
    assert out["tokens_in_window"] == 3

"""Test fixtures.

Analog of the reference's python/ray/tests/conftest.py: `ray_start_regular`
boots a real one-process-tree cluster per test; `ray_start_cluster` yields a
multi-raylet single-host Cluster (the reference's multi-node-without-a-cluster
trick, cluster_utils.py:99).

JAX is forced onto a virtual 8-device CPU mesh BEFORE first import so sharding
tests exercise real multi-device paths without TPU hardware.
"""

import faulthandler
import os
import shutil
import signal
import sys
import tempfile
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_NUM_TPUS", "0")
# Dynamic backup for the graftlint static affinity checks: @loop_only /
# @blocking markers (ray_tpu/_private/concurrency.py) install cheap runtime
# asserts when this is set BEFORE first import. Driven by the lease/worker
# test modules (test_leases, test_basic, test_actors, test_cancel, ...);
# enabled process-wide because marker behavior binds at import and the suite
# shares one interpreter — worker subprocesses inherit it, so the asserts
# also run inside every spawned worker's IO loop and exec thread.
os.environ.setdefault("RAY_TPU_DEBUG_AFFINITY", "1")

# One compile cache for the whole run, in the run's temp directory: the six
# xdist workers and the worker processes of every test's cluster share it. The
# suite is bound by the CPU's compiles, not by waits: the plain references run
# op by op, some 450 little programs a sequence length at ~40 ms each, the same
# ones in test after test, and the toy engines of one file compile alike. With
# the cache a file's second run takes 0.4 of its first (test_serve_llm_pattern,
# PERF.md section 7). A caller's own directory is left alone; ours is removed
# as the session ends.
_OUR_JAX_CACHE = os.path.join(
    tempfile.gettempdir(),
    "ray_tpu_tests_jax_cache_%d" % (os.getppid() if "PYTEST_XDIST_WORKER" in os.environ else os.getpid()),
)
if os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _OUR_JAX_CACHE) == _OUR_JAX_CACHE:
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_sessionfinish(session):
    if "PYTEST_XDIST_WORKER" not in os.environ:
        shutil.rmtree(_OUR_JAX_CACHE, ignore_errors=True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`); wide sweeps and "
        "long soak tests",
    )


# tests/benchmark/ belongs to the benchmark (BENCHMARK.json "paths"): a PR that
# adds a configuration may add files there and may not edit one. A test there
# that a second architecture makes wrong is marked here until a `benchmark` PR
# rewords it (PERF.md section 7), and its intent is tested in a new file. The
# marks are strict: a test that is reworded and passes fails its mark, so that
# the mark goes with the rewording and cannot outlive it. Likewise a test that
# pins the END of a list that a later PR may only append to.
_WRONG_SINCE_A_SECOND_ARCHITECTURE = {  # the first four (PR 32)
    "test_bench_costs.py::test_the_configuration_files_hold_the_published_widths": (
        "holds EVERY configuration of BENCHMARK.json to Mistral-7B's widths; since PR 32 one is "
        "GLM-4.7-Flash. test_bench_glm.py::test_each_configuration_holds_its_own_published_widths "
        "holds each to its own"
    ),
    "test_bench_span_metrics.py::test_expected_metrics_lists_each_span_metric_for_exactly_its_cells": (
        "holds that BENCHMARK.json's per_layer ENDS with PR 26's twelve span metrics and that two "
        "cells report them; PR 32 appends six metrics and two cells. "
        "test_bench_glm.py::test_the_span_metrics_stand_and_new_cells_are_only_appended stands in"
    ),
    "test_bench_span_metrics.py::test_benchmark_json_declares_them_and_the_parked_copy_is_gone": (
        "holds each span metric's workloads to exactly PR 26's cells; PR 32 appends its two. "
        "test_bench_glm.py::test_the_span_metrics_stand_and_new_cells_are_only_appended stands in"
    ),
    "test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[rollout-long]": (
        "holds every serving mix under 2560 tokens a request, one configuration's max_model_len; "
        "rollout-long runs under 4096. test_bench_glm.py::test_every_mix_fits_the_configurations_"
        "that_run_it holds each mix to its own cells' limits, and the seeds' equal load there too"
    ),
    # And since a third (PR 35):
    "test_bench_glm.py::test_each_configuration_holds_its_own_published_widths": (
        "looks every configuration's architecture up in a table of two and holds every `reduced` to "
        "['num_hidden_layers']; since PR 35 one is Trinity-Mini, whose cut of depth takes its leading dense "
        "layers and its list of layer types with it. test_bench_trinity.py::"
        "test_each_configuration_holds_its_own_published_keys holds each to its own, by architecture"
    ),
    "test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[rollout-longctx]": (
        "holds every serving mix under 2560 tokens a request, one configuration's max_model_len; "
        "rollout-longctx runs under 9216. test_bench_trinity.py::test_every_mix_fits_the_cells_that_send_it "
        "holds each mix to its own cells' limits, a session's later turns too, and the seeds' equal load"
    ),
    # And since PR 38 appends seven metrics (ISSUE 38 asked for them BEFORE the cache pair, to spare this pin, and
    # for no new mark here; the driver's check reads an entry in the middle as a change to `cache_attention_ms`):
    "test_bench_trinity.py::test_the_mix_is_the_issues": (
        "holds BENCHMARK.json's per_layer to END with PR 35's cache pair; PR 38 appends the seven metrics of a "
        "token's way back behind it. test_bench_delivery.py::test_trinitys_mix_is_the_issues_with_the_seven_"
        "behind_its_cache_pair holds the rest of it, and the pair at [-9:-7]"
    ),
    # And since a fourth configuration, whose cell's requests are 8k tokens long (PR 41):
    "test_bench_delivery.py::test_the_seven_are_declared_for_exactly_the_two_cells": (
        "holds BENCHMARK.json's per_layer to END with PR 38's seven, to count 41 and the seven to two cells; PR 41 "
        "appends its four behind them and its cell to the seven's lists. test_bench_olmo_hybrid.py::"
        "test_the_new_entries_are_appended_behind_what_was_there holds the seven together behind the cache pair, "
        "their two cells with PR 41's appended, and PR 41's four behind them, without a pin on the END"
    ),
    "test_bench_delivery.py::test_trinitys_mix_is_the_issues_with_the_seven_behind_its_cache_pair": (
        "holds Trinity's cell and configuration to be the LAST, the cells to be seven and the cache pair to be "
        "Trinity's alone; PR 41 appends a cell and a configuration, and its four full layers report the pair. "
        "test_bench_olmo_hybrid.py::test_trinitys_mix_is_still_the_issues holds the rest of it"
    ),
    "test_bench_trinity.py::test_each_configuration_holds_its_own_published_keys": (
        "looks every configuration's architecture up in a table of three and holds the set of configurations to "
        "three models'; since PR 41 one is Olmo-Hybrid-7B. test_bench_olmo_hybrid.py::"
        "test_each_configuration_holds_its_own_published_keys holds each to its own, by architecture"
    ),
    "test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[longdoc-8k]": (
        "holds every serving mix under 2560 tokens a request, one configuration's max_model_len; longdoc-8k runs "
        "under 8192. test_bench_olmo_hybrid.py::test_every_mix_fits_the_cells_that_send_it holds each mix to its "
        "own cells' limits, and the seeds' equal load there too"
    ),
    # And since a fifth configuration, whose cell reports the cache pair, the state's two of the linear four and the seven too (PR 43):
    "test_bench_olmo_hybrid.py::test_the_new_entries_are_appended_behind_what_was_there": (
        "holds Olmo-Hybrid's four metrics to its cell alone and the seven of a token's way back to three cells; PR 43 "
        "appends its cell to the seven's lists and to linear_state_*'s (their readers take the Mamba-2 state from its costs.py; the scan's two "
        "move ttft_p90_ms, which the cell does not report, and keep their list). "
        "test_bench_nemotron_h.py::test_the_new_entries_are_appended_behind_what_was_there holds the seven behind "
        "the cache pair, the four behind them, and PR 43's two behind those, without a pin on the END"
    ),
    "test_bench_olmo_hybrid.py::test_trinitys_mix_is_still_the_issues": (
        "holds the cache pair to Trinity's and Olmo-Hybrid's cells; the two attention blocks of PR 43's cut report it "
        "too. test_bench_nemotron_h.py::test_trinitys_mix_is_still_the_issues holds the rest of it"
    ),
    # And since a seventh configuration, whose cell's requests are 12k tokens long and report the share of passes with a chunk (PR 47):
    "test_bench_traffic.py::test_two_seeds_offer_the_same_token_load[longdoc-12k]": (
        "holds every serving mix under 2560 tokens a request, one configuration's max_model_len; longdoc-12k runs "
        "under 12288. test_bench_xing.py::test_the_mix_is_the_issues_and_fits_the_cell holds each serving mix to the "
        "limit of the configuration that runs it, and the seeds' equal load there too"
    ),
    "test_bench_nemotron_h.py::test_the_new_entries_are_appended_behind_what_was_there": (
        "holds prefill_pass_share_pct to Nemotron's cell alone; PR 47 appends its cell to that list (ISSUE 47: the "
        "prefill lane is busy in most of its passes). test_bench_xing.py::"
        "test_the_new_entries_are_appended_behind_what_was_there holds PR 43's two behind Olmo-Hybrid's four, PR 47's "
        "two behind those and every list's order, without a pin on the END"
    ),
    # And since PR 52 appends the eight metrics of a start, six to every serving cell's list and two to every training cell's:
    "test_bench_span_metrics.py::test_a_traced_line_carries_them_and_a_program_without_spans_leaves_them_out[serve16.batch-decode]": (
        "holds a line built from PR 24's recording of get_stats() to carry EVERY metric declared for the cell; that "
        "recording is a start without stage records, and PR 52's six readers find nothing in it. test_bench_setup_stages.py::"
        "test_a_traced_line_carries_the_span_metrics_and_a_start_without_records_leaves_the_six_out holds the rest of it"
    ),
    "test_bench_span_metrics.py::test_a_traced_line_carries_them_and_a_program_without_spans_leaves_them_out[serve16.chat-open]": (
        "as its other case: PR 24's recording holds no record of PR 52's. test_bench_setup_stages.py::"
        "test_a_traced_line_carries_the_span_metrics_and_a_start_without_records_leaves_the_six_out stands in"
    ),
    "test_bench_glm.py::test_the_span_metrics_stand_and_new_cells_are_only_appended": (
        "counts 24 and 18 metrics on the traced lines of the two first cells; PR 52 appends six to each. "
        "test_bench_setup_stages.py::test_the_span_metrics_stand_where_they_were_and_a_cells_count_is_its_lists holds "
        "the twelve where they were and each cell's count to the lists that name it"
    ),
    "test_bench_mellum.py::test_the_job_is_the_issues_and_nothing_but_files_and_appended_entries_came": (
        "holds the SET of Mellum's per-layer metrics to PR 50's five; PR 52 appends the two of a trainer's start. "
        "test_bench_setup_stages.py::test_the_training_cells_report_what_they_did_and_the_two_of_their_start holds the rest "
        "of it, and the two behind the five"
    ),
    "test_bench_xing.py::test_the_new_entries_are_appended_behind_what_was_there": (
        "holds the SET of Xing's traced metrics to what PR 47 left; PR 52 appends the six of a replica's start. "
        "test_bench_setup_stages.py::test_xings_cell_reports_what_it_did_and_the_six_of_its_start holds the rest of it, "
        "and the six behind everything else"
    ),
    # And since a ninth configuration, whose cell reports the cache pair and whose two metrics come behind PR 52's eight (PR 54):
    "test_bench_nemotron_h.py::test_trinitys_mix_is_still_the_issues": (
        "holds the cache pair to Trinity's, Olmo-Hybrid's and Nemotron's cells; the two attention layers of PR 54's cut "
        "report it too. test_bench_lfm2.py::test_trinitys_mix_is_still_the_issues holds the rest of it"
    ),
    "test_bench_setup_stages.py::test_benchmark_json_gains_exactly_the_eight_at_the_end_of_per_layer": (
        "holds BENCHMARK.json's per_layer to END with PR 52's eight, the cells to be eleven and the six of a replica's "
        "start to eight cells; PR 54 appends its two metrics behind the eight and its cell to the six's lists. "
        "test_bench_lfm2.py::test_the_new_entries_are_appended_behind_what_was_there holds the eight together behind the "
        "49, each as it was declared, and PR 54's two behind them, without a pin on the END"
    ),
    # And since a tenth configuration, whose cell joins every list LFM2's is in but the conv mixer's two (PR 56):
    "test_bench_lfm2.py::test_the_new_entries_are_appended_behind_what_was_there": (
        "holds the six of a replica's start to the eight serving cells it knew and its own, and a traced line of LFM2's cell "
        "to one set of metrics among twelve cells; PR 56 appends its cell to the six's lists and to every other list LFM2's cell "
        "is in but short_conv_*. test_bench_sdar.py::test_the_new_entries_are_appended_behind_what_was_there holds PR 52's eight "
        "behind the 49, PR 54's two behind them, PR 56's three behind those and every list's order, without a pin on the END"
    ),
    # And since an eleventh configuration, whose cell joins every list LFM2's is in but the conv mixer's and the K/V cache's
    # pairs, and the latent pair's and the held share's (PR 61):
    "test_bench_sdar.py::test_the_new_entries_are_appended_behind_what_was_there": (
        "holds the six of a replica's start to the nine serving cells it knew and its own, and the cells to thirteen with "
        "SDAR's the last; PR 61 appends its cell to the six's lists and to every other list LFM2's cell is in but short_conv_* "
        "and cache_attention_*. test_bench_longcat.py::test_the_new_entries_are_appended_behind_what_was_there holds PR 52's "
        "eight behind the 49, PR 54's two, PR 56's three and PR 61's two behind them and every list's order, without a pin on the END"
    ),
    "test_bench_setup_stages.py::test_xings_cell_reports_what_it_did_and_the_six_of_its_start": (
        "holds moe_held_share_pct to Nemotron's cell alone and the latent pair to GLM's and Xing's; PR 61's cell holds a share "
        "of its experts and attends over a latent pool, and is appended to both. test_bench_longcat.py::"
        "test_the_new_entries_are_appended_behind_what_was_there holds those lists with the new cell last, and what Xing's "
        "cell reports"
    ),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        for name, why in _WRONG_SINCE_A_SECOND_ARCHITECTURE.items():
            if item.nodeid.endswith(name):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))


# A test that hangs fails by its name. The driver cuts the whole run at its own
# limit and a cut run names nothing (`failed: []`), so no one call may outlast
# this: the longest tier-1 call is under 70 s alone and ~2.3x that with six
# workers on eight cores. xdist's workers run their tests on the main thread
# (execnet's `main_thread_only`), where a signal's handler may raise.
_TEST_LIMIT_S = 300.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if threading.current_thread() is not threading.main_thread():
        yield  # no handler can raise here; the driver's limit is all there is
        return

    def _expired(signum, frame):
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        # Failed is a BaseException: no `except Exception` of a retry loop
        # that the test hangs in can swallow it.
        pytest.fail(
            f"{item.nodeid} ran past its limit of {_TEST_LIMIT_S:g} s; every thread's stack:\n{stacks}",
            pytrace=False,
        )

    before = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        yield cluster
    finally:
        cluster.shutdown()

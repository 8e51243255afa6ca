"""Serve: on the idle engine, median over probe pairs of the time to a first
token over HTTP minus that of ``engine.submit`` inside the replica."""


def read(result):
    return result["clock"].get("serve_path_overhead_ms")

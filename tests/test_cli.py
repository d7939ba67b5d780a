"""Smoke tests for the operator CLI (repro.launch.migrate) — flag
parsing, listings, exit codes, and short end-to-end runs with the cheap
hash-fold consumer."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache
from repro.launch.migrate import main

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _compile_cache_off_after():
    """``main()`` turns on JAX's persistent compile cache for the whole
    process; turn it off again so later tests do not write to it."""
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_list_strategies_prints_registry(capsys):
    assert main(["--list-strategies"]) == 0
    out = capsys.readouterr().out
    for name in ("stop_and_copy", "ms2m_individual", "ms2m_cutoff",
                 "ms2m_statefulset", "ms2m_precopy", "ms2m_adaptive"):
        assert name in out
    assert "wants_cutoff" in out  # control-plane flags are shown


def test_list_topologies_prints_presets(capsys):
    assert main(["--list-topologies"]) == 0
    out = capsys.readouterr().out
    for name in ("flat", "two_zone", "edge_wan"):
        assert name in out


@pytest.mark.parametrize("argv", [
    ["--no-such-flag"],
    ["--strategy", "not_a_strategy"],
    ["--topology", "not_a_topology"],
    ["--compression", "not_a_codec"],
    ["--strat", "ms2m_individual"],       # abbreviations are disabled
])
def test_bad_flags_exit_2(argv):
    with pytest.raises(SystemExit) as ei:
        main(argv)
    assert ei.value.code == 2


def test_run_hash_consumer_default_strategy(capsys, tmp_path):
    rc = main(["--hash-consumer", "--rate", "6",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["strategy"] == "ms2m_individual"
    assert row["verified"] is True
    assert row["attempts"] == 1
    assert "[migrate] downtime=" in out


@pytest.mark.parametrize("strategy,extra", [
    ("stop_and_copy", []),
    ("ms2m_cutoff", ["--t-replay-max", "30"]),
    ("ms2m_precopy", ["--compression", "int8"]),
    ("ms2m_statefulset", ["--topology", "two_zone"]),
])
def test_strategy_topology_compression_combinations(capsys, tmp_path,
                                                    strategy, extra):
    rc = main(["--hash-consumer", "--rate", "6", "--strategy", strategy,
               "--registry", str(tmp_path / "reg")] + extra)
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["strategy"] == strategy and row["verified"] is True


def test_events_flag_prints_trace(capsys, tmp_path):
    rc = main(["--hash-consumer", "--rate", "6", "--events",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 0
    assert '"kind": "phase"' in out
    assert '"kind": "migration_end"' in out


def test_fault_flag_recovers_via_retry(capsys, tmp_path):
    rc = main(["--hash-consumer", "--rate", "6",
               "--fault", "node_flap@30,node=node1,duration=60",
               "--max-attempts", "3", "--retry-backoff", "1",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["verified"] is True and row["attempts"] >= 2


def test_fault_flag_exhausted_retries_reports_failure(capsys, tmp_path):
    rc = main(["--hash-consumer", "--rate", "6",
               "--fault", "registry_outage@10.5,duration=500",
               "--max-attempts", "2",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 1
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["failed"] is True and row["attempts"] == 2
    assert row["rolled_back"] is True and row["source_serving"] is True
    assert "FAILED after 2 attempt(s)" in out


def test_bad_fault_spec_is_a_clear_error(tmp_path):
    with pytest.raises(ValueError, match="fault spec"):
        main(["--hash-consumer", "--fault", "bogus",
              "--registry", str(tmp_path / "reg")])


def test_list_strategies_includes_serving_handoff(capsys):
    assert main(["--list-strategies"]) == 0
    assert "serving_handoff" in capsys.readouterr().out


def test_serving_workload_handoff(capsys, tmp_path):
    rc = main(["--workload", "serving", "--hash-consumer", "--rate", "8",
               "--strategy", "serving_handoff",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["strategy"] == "serving_handoff"
    assert row["exactly_once"] is True
    assert row["state_verified"] is True
    assert row["lost"] == 0
    assert row["latency"]["p99"] is not None
    assert "[migrate] p50=" in out


def test_serving_workload_baseline_scheme(capsys, tmp_path):
    rc = main(["--workload", "serving", "--hash-consumer", "--rate", "8",
               "--strategy", "ms2m_statefulset",
               "--registry", str(tmp_path / "reg")])
    out = capsys.readouterr().out
    assert rc == 0
    row = json.loads(out[:out.rindex("}") + 1])
    assert row["exactly_once"] is True and row["state_verified"] is True


def test_compile_cache_goes_where_the_environment_places_it(monkeypatch,
                                                           tmp_path):
    placed = tmp_path / "placed"
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(placed))
    fixed = compile_cache.DEFAULT_CACHE_DIR
    before = sorted(os.listdir(fixed)) if os.path.isdir(fixed) else []
    assert compile_cache.enable_compile_cache() == str(placed)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        compilation_cache.reset_cache()
        jax.jit(lambda x: jnp.cos(x) * 5 - 2)(jnp.ones(11)).block_until_ready()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    assert any(name.endswith("-cache") for name in os.listdir(placed))
    after = sorted(os.listdir(fixed)) if os.path.isdir(fixed) else []
    assert after == before


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_path(
        monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()

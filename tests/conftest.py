"""Test env: force the CPU platform with a virtual 8-device mesh so
multi-chip sharding paths compile and run without TPU hardware (the
analog of the reference's `local-cluster[...]` pseudo-distributed tests,
integration_tests/README.md:205)."""
import os

# Lockdep witness for the WHOLE suite: must be in the env BEFORE the
# engine imports so lock factories wrap at creation (runtime/lockdep.py).
# Any lock-order cycle or pool self-wait the tests drive the engine into
# raises at formation time instead of hanging the suite.
os.environ.setdefault("SRTPU_LOCKDEP", "1")
# Resource-ledger witness for the WHOLE suite (runtime/ledger.py): every
# query the tests run must end every terminal state (FINISHED, CANCELLED,
# TIMED_OUT) with balanced query-scoped acquire/release counters, or
# QueryManager._finalize raises ResourceLeakError and the test fails.
os.environ.setdefault("SRTPU_LEDGER", "1")
# Data-race witness for the WHOLE suite (runtime/racedep.py),
# record-only: Eraser lockset tracking on the instrumented shared
# structures (program cache observed table, telemetry registry,
# result-cache LRU, shuffle map slots, metric sets). Record-only so a
# witnessed collapse surfaces through tests/test_racedep.py's
# clean-report assertion instead of raising at an arbitrary point
# mid-suite.
os.environ.setdefault("SRTPU_RACEDEP", "1")
os.environ.setdefault("SRTPU_RACEDEP_RAISE", "0")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the CPU backend with eight virtual devices whatever the
# machine holds: make the CPU the default platform before the first
# device query (same effect as JAX_PLATFORMS=cpu, which the driver sets).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import spark_rapids_tpu as st  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy variants excluded from tier-1 (-m 'not slow')")


@pytest.fixture(autouse=True, scope="module")
def _drop_program_cache_per_module():
    """Release the process-global program cache at module boundaries.
    Every live XLA:CPU executable pins ~10-20 memory mappings; one
    long pytest process compiling the whole suite's worth of programs
    walks into vm.max_map_count (65530), after which LLVM's JIT mmap
    fails and the NEXT compile segfaults. Per-instance jits used to
    die with their exec trees; this restores that lifetime at module
    granularity while keeping cross-instance sharing within a module
    (which is what the cache tests assert)."""
    yield
    from spark_rapids_tpu.runtime import program_cache, result_cache
    program_cache.clear()
    # cached Arrow results/fragments pin host bytes and index entries by
    # on-disk paths; a module's tmp_path tables must not leak hits (or
    # stale invalidation state) into the next module
    result_cache.clear()
    # observed-cardinality calibration is session-scoped state keyed on
    # structural fingerprints; one module's harvested row counts must
    # not steer another module's join planning
    from spark_rapids_tpu.plan import stats as _stats
    _stats.clear_calibration()
    # fleet membership is process state backed by an on-disk peer
    # directory (usually a tmp_path): leave the fleet, stop the peer
    # cache server, and uninstall the result-cache dispatcher so a
    # later module never consults a dead directory
    import sys
    if "spark_rapids_tpu.fleet" in sys.modules:
        from spark_rapids_tpu import fleet
        fleet.reset()


@pytest.fixture(scope="session")
def session():
    return st.TpuSession({
        "spark.rapids.tpu.sql.batchSizeRows": 4096,
    })

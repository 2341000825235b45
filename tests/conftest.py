"""Test bootstrap: force JAX onto a virtual 8-device CPU platform BEFORE jax
initializes, so sharding/mesh tests run without TPU hardware."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never take an attached chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak, excluded from the fast tier "
        "(runs in the CI_FULL full-suite tier)")


if os.environ.get("SRT_LEAK_GATE"):
    # CI leak gate: after the whole session, any resource still tracked by
    # the process-wide MemoryCleaner is a leak and fails the run (the
    # reference treats shutdown leaks as bugs, Plugin.scala:581-596).
    # Catalog-held shuffle blocks are owned state released by their atexit
    # hooks, so they are freed explicitly before the check.
    def pytest_sessionfinish(session, exitstatus):
        if exitstatus != 0:
            return
        from spark_rapids_tpu.execs.compiled_join import clear_dim_cache
        from spark_rapids_tpu.memory.cleaner import MemoryCleaner
        from spark_rapids_tpu.shuffle.ici import IciShuffleCatalog
        # free OWNED state first, same as MemoryCleaner._at_shutdown, so
        # the gate checks exactly what the shutdown report would show
        IciShuffleCatalog._shutdown_instance()
        clear_dim_cache()
        leaks = MemoryCleaner.get().check_leaks()
        if leaks:
            import sys
            print(f"\n[LEAK GATE] {len(leaks)} leaked device resources:",
                  file=sys.stderr)
            for item in leaks[:20]:
                print(f"  {item}", file=sys.stderr)
            session.exitstatus = 1


if os.environ.get("SRT_LEAK_PER_TEST"):
    # leak-hunting mode: capture creation stacks and attribute each leaked
    # resource to the test that created it (enable with SRT_LEAK_PER_TEST=1)
    from spark_rapids_tpu.memory.cleaner import MemoryCleaner
    MemoryCleaner.get().set_debug(True)

    @pytest.fixture(autouse=True)
    def _leak_per_test(request):
        cleaner = MemoryCleaner.get()
        cleaner.set_debug(True)
        before = {r.token for r in cleaner.live_resources()}
        yield
        after = MemoryCleaner.get()
        if after is not cleaner:  # a test reset the singleton
            after.set_debug(True)
            return
        new = [r for r in cleaner.live_resources() if r.token not in before]
        if new:
            import sys
            print(f"\n[LEAK] {request.node.nodeid}: "
                  f"{len(new)} new live resources", file=sys.stderr)
            for r in new:
                print(f"  {r.kind} (token {r.token})\n{r.stack or ''}",
                      file=sys.stderr)


@pytest.fixture()
def collective_spy(monkeypatch):
    """Records each exchange materialization's collective verdict (True =
    the mesh all_to_all ran, False = per-map fallback). Shared by the mesh
    shuffle + mesh data-plane suites."""
    from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec
    runs = []
    orig = TpuShuffleExchangeExec._try_materialize_collective

    def spy(self, sid, ctx):
        used = orig(self, sid, ctx)
        runs.append(used)
        return used

    monkeypatch.setattr(TpuShuffleExchangeExec,
                        "_try_materialize_collective", spy)
    return runs


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_state():
    """The full suite compiles thousands of XLA CPU executables in one
    process; unbounded accumulation has produced allocator segfaults deep
    into the run. Dropping jax's compilation caches between modules bounds
    the live-executable set (re-compiles within a module stay cached)."""
    yield
    import gc
    jax.clear_caches()
    gc.collect()


@pytest.fixture()
def session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({})


@pytest.fixture()
def cpu_session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.sql.enabled": "false"})

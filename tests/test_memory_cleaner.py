"""Leak tracking + double-close discipline (reference MemoryCleaner
shutdown leak check, Plugin.scala:581-596, and
GpuColumnVector refcount double-close logging)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.memory.cleaner import DoubleCloseError, MemoryCleaner
from spark_rapids_tpu.memory.spill import SpillableColumnarBatch


def _batch(n=64):
    col = TpuColumnVector.from_arrow(pa.array(np.arange(n, dtype=np.int64)))
    return TpuColumnarBatch([col], n, names=["v"])


def test_clean_lifecycle_leaves_no_leaks():
    cleaner = MemoryCleaner.reset_for_tests()
    with SpillableColumnarBatch(_batch()) as sb:
        sb.get_batch()
    assert cleaner.check_leaks() == []
    assert cleaner.double_closes == 0


def test_unclosed_batch_is_reported_as_leak():
    cleaner = MemoryCleaner.reset_for_tests()
    sb = SpillableColumnarBatch(_batch())
    leaks = cleaner.check_leaks()
    assert len(leaks) == 1 and "SpillableColumnarBatch" in leaks[0]
    with pytest.raises(AssertionError, match="leaked device resources"):
        cleaner.check_leaks(raise_on_leak=True)
    sb.close()
    assert cleaner.check_leaks() == []


def test_double_close_counted_and_raises_in_debug():
    cleaner = MemoryCleaner.reset_for_tests()
    sb = SpillableColumnarBatch(_batch())
    sb.close()
    sb.close()  # silent count in non-debug mode
    assert cleaner.double_closes == 1

    cleaner = MemoryCleaner.reset_for_tests()
    cleaner.set_debug(True)
    sb2 = SpillableColumnarBatch(_batch())
    sb2.close()
    with pytest.raises(DoubleCloseError):
        sb2.close()


def test_debug_mode_captures_creation_stack():
    cleaner = MemoryCleaner.reset_for_tests()
    cleaner.set_debug(True)
    sb = SpillableColumnarBatch(_batch())
    leaks = cleaner.check_leaks()
    assert len(leaks) == 1
    assert "test_memory_cleaner" in leaks[0]  # stack names this file
    sb.close()


def test_close_after_reset_lands_in_creating_instance():
    """A spillable created under one cleaner instance
    and closed after a reset_for_tests (long-lived caches, shutdown hooks)
    must unregister from the CREATING instance's book — otherwise the old
    instance's atexit report shows a phantom leak the gate can't see."""
    creating = MemoryCleaner.reset_for_tests()
    sb = SpillableColumnarBatch(_batch(5))
    current = MemoryCleaner.reset_for_tests()  # singleton swapped mid-life
    sb.close()
    assert creating.check_leaks() == []
    assert creating.double_closes == 0
    assert current.double_closes == 0  # token never touched the new book
    MemoryCleaner.reset_for_tests()


def test_leak_gate_fails_on_injected_leak(tmp_path):
    """The CI gate must demonstrably fail when a leak is injected: run a
    one-test pytest session (with this repo's conftest) that abandons a
    SpillableColumnarBatch, and assert SRT_LEAK_GATE turns it red."""
    import os
    import shutil
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(repo, "tests", "conftest.py"),
                tmp_path / "conftest.py")
    (tmp_path / "test_injected_leak.py").write_text(
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "from spark_rapids_tpu.columnar.batch import TpuColumnarBatch\n"
        "from spark_rapids_tpu.columnar.vector import TpuColumnVector\n"
        "from spark_rapids_tpu.memory.spill import SpillableColumnarBatch\n"
        "LEAKED = []\n"
        "def test_leak():\n"
        "    col = TpuColumnVector.from_arrow(\n"
        "        pa.array(np.arange(8, dtype=np.int64)))\n"
        "    LEAKED.append(SpillableColumnarBatch(\n"
        "        TpuColumnarBatch([col], 8, names=['v'])))\n")
    env = dict(os.environ, SRT_LEAK_GATE="1", PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "[LEAK GATE]" in proc.stderr, proc.stdout + proc.stderr
    assert "SpillableColumnarBatch" in proc.stderr


def test_session_conf_enables_debug():
    from spark_rapids_tpu.session import TpuSession
    cleaner = MemoryCleaner.reset_for_tests()
    assert not cleaner.debug
    TpuSession({"spark.rapids.memory.debug.leakTracking": "true"})
    assert MemoryCleaner.get().debug
    MemoryCleaner.reset_for_tests()

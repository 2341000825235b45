"""Device time and idle time by `srt.*` phase (ISSUE 35,
`chipbench/phase_table.py`): the join of each device program to the phase
that launched it by the trace's own launch ids, over traces recorded on a
TPU v5e; `trace.reduce` unchanged; the reader `phase_device`.
"""

import os
from types import SimpleNamespace

import pytest

from chipbench import phase_table as pt
from chipbench import trace as tm
from chipbench.readers import phase_device

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "chipbench", "testdata")
#: what `trace.reduce` gave on small.xplane.pb before ISSUE 35, every key
SMALL_REDUCED = {
    "busy_s": 4.963e-06, "window_s": 0.053901619, "idle_share": 0.9999079248435933,
    "devices": 1, "launches": 3.0, "ops_line": ["XLA Ops"],
    "device_ops": [["jit__lambda", 4.991e-06]],
    "idle_gaps": [["host.sleep", 0.051755878], ["chipbench.query b", 0.001538715],
                  ["DoEnqueueProgram", 0.000602056], ["chipbench.query a", 7e-09]]}


@pytest.fixture(scope="module")
def small():
    return pt.load(os.path.join(DATA, "small.xplane.pb"))


@pytest.fixture(scope="module")
def phases():
    """record_phase_trace.py's trace: two queries; `srt.query` launches
    p_root, `srt.stage.launch` in it p_stage twice, `srt.scan.page_walk` in
    it sleeps 40 ms then launches p_scan; a worker thread, in the query's
    span, launches p_segment three times in `srt.segment.launch`."""
    lines, stats = pt.load(os.path.join(DATA, "phases.xplane.pb"))
    return lines, stats, pt.table(lines, stats)


def test_reduce_reads_what_it_read_before(small):
    lines, _ = small
    assert tm.reduce(lines) == SMALL_REDUCED
    assert tm.reduce(tm.load(os.path.join(DATA, "small.xplane.pb"))) == SMALL_REDUCED


def test_every_module_links_and_the_table_holds_the_window(small):
    tab = pt.table(*small)
    assert tab["linked_share"] == 1.0 and tab["queries"] == 5
    rows = tab["phases"].values()
    assert sum(r["launches"] for r in rows) == SMALL_REDUCED["launches"]
    assert sum(r["device_s"] for r in rows) == pytest.approx(4.991e-06, abs=1e-15)
    idle = SMALL_REDUCED["window_s"] - SMALL_REDUCED["busy_s"]
    assert sum(r["idle_s"] for r in rows) == pytest.approx(idle, abs=1e-12)
    # no srt.* annotation in that trace: the launches have no phase, and the
    # 50 ms sleep between the queries lies outside them
    assert tab["phases"][pt.NO_PHASE]["launches"] == 3
    assert tab["phases"][pt.OUTSIDE]["idle_s"] > 0.050
    assert tab["counts"] == {}


def test_stacks_nest_outermost_first():
    import numpy as np
    starts = np.array([0.0, 0.0, 5.0, 20.0])      # a and b start together: a encloses b
    ends = np.array([30.0, 10.0, 8.0, 25.0])
    names = ["a", "b", "c", "d"]
    got = pt._stacks_at(starts, ends, names, np.array([22.0, 1.0, 6.0, 12.0, 40.0]))
    assert got == [("a", "d"), ("a", "b"), ("a", "b", "c"), ("a",), ()]


#: record_phase_trace.py: the phase path that launches each program, and
#: how many times a query
LAUNCHED_IN = {"jit_p_root": ("query", 1), "jit_p_stage": ("query/stage.launch", 2),
               "jit_p_scan": ("query/scan.page_walk", 1),
               "jit_p_segment": ("segment.launch", 3)}


def test_recorded_modules_link_to_the_phase_that_launched_them(phases):
    lines, _, tab = phases
    red = tm.reduce(lines)
    assert tab["linked_share"] == 1.0 and tab["queries"] == 2
    assert tab["counts"] == {"query": 2, "stage.launch": 2, "scan.page_walk": 2,
                             "segment.launch": 2}
    seconds = dict(red["device_ops"])
    assert set(seconds) == set(LAUNCHED_IN)
    for program, (key, per_query) in LAUNCHED_IN.items():
        row = tab["phases"][key]
        assert row["launches"] == 2 * per_query, key
        assert [p for p, _ in tab["programs"][key]] == [program], key
        assert row["device_s"] == pytest.approx(seconds[program], rel=1e-9), key
    # everything the window holds is in some row: programs and idle time
    rows = tab["phases"].values()
    assert sum(r["device_s"] for r in rows) == pytest.approx(sum(seconds.values()), rel=1e-9)
    assert sum(r["launches"] for r in rows) == red["launches"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(r["idle_s"] for r in rows) == pytest.approx(idle, rel=1e-9)
    # the device's clock runs behind the host's by about a millisecond
    assert 0 < tab["clock_lag_ms"]["/device:TPU:0"] < 5


def test_the_gap_goes_to_the_phase_its_thread_stood_in(phases):
    _, _, tab = phases
    walk = tab["phases"]["query/scan.page_walk"]["idle_s"]
    # two queries, each a 40 ms sleep (record_phase_trace.SLEEP_S) before
    # p_scan, plus the launch itself
    assert 2 * 0.040 < walk < 2 * 0.040 + 0.010
    assert pt.select(tab, "scan.page_walk")["idle_s"] == walk
    assert pt.select(tab, "query", nested=True)["idle_s"] > walk


def test_the_reader_per_query_and_per_count(phases):
    _, _, tab = phases
    ctx = SimpleNamespace(phase_table=tab)
    dev = tab["phases"]["query/stage.launch"]["device_s"]
    assert phase_device.read(ctx, "stage.launch", "device_ms", "query") == \
        pytest.approx(dev * 1e3 / 2)
    assert phase_device.read(ctx, "stage.launch", "launches", "count") == 2.0
    assert phase_device.read(ctx, "segment.launch", "launches", "count") == 3.0
    nested = phase_device.read(ctx, "query", "device_ms", "query", nested=True)
    own = phase_device.read(ctx, "query", "device_ms", "query")
    assert nested > own > 0
    # a phase the program never opened, or no table: nothing
    assert phase_device.read(ctx, "exchange.split", "device_ms", "query") is None
    assert phase_device.read(SimpleNamespace(), "query", "device_ms", "query") is None
    with pytest.raises(ValueError):
        phase_device.read(ctx, "query", "busy_ms", "query")
    with pytest.raises(ValueError):
        phase_device.read(ctx, "query", "device_ms", "batch")

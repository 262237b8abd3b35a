"""The span recorder (``repro.utils.trace``): a bounded buffer that tells a
reader when it lost part of a window, safe across threads, and a profiler
copy that holds the leaf spans alone under their fixed names."""
import glob
import os
import sys
import threading
import time

import pytest

from repro.utils import trace
from repro.utils.trace import Record, Recorder


def test_buffer_is_bounded_and_keeps_the_newest():
    rec = Recorder(maxlen=4)
    for i in range(10):
        rec.count("c", i)
    held = rec.window(-1.0, time.monotonic())
    assert held is None                      # records of the range were lost
    summ = rec.summary()["c"]
    assert summ["count"] == 4
    assert summ["total"] == 6 + 7 + 8 + 9 and summ["max"] == 9


def test_window_is_none_only_when_its_range_lost_records():
    rec = Recorder(maxlen=3)
    rec.record("old", 0.0, 1.0)
    rec.record("old", 1.0, 2.0)
    for t in (10.0, 11.0, 12.0):
        rec.record("new", t, t + 0.5)        # drops both "old" records
    assert rec.window(1.5, 20.0) is None     # "old" ending at 2.0 is gone
    got = rec.window(5.0, 20.0)
    assert [r.name for r in got] == ["new"] * 3
    assert rec.window(10.6, 11.6) == [Record("new", 11.0, 11.5, 0.5, {})]


def test_record_takes_explicit_times_and_ids():
    rec = Recorder()
    rec.record("tasm.queue", 3.0, 3.25, req=7, batch=2)
    (r,) = rec.window(3.0, 4.0)
    assert (r.t0, r.t1, r.value, r.ids) == (3.0, 3.25, 0.25,
                                            {"req": 7, "batch": 2})
    assert rec.window(3.26, 4.0) == []       # a record belongs where it ends


def test_span_times_its_block_and_reads_while_open():
    rec = Recorder()
    with rec.span("s", profile=False, video="cam0") as sp:
        time.sleep(0.01)
        during = sp.seconds
        time.sleep(0.01)
    assert 0.01 <= during < sp.seconds
    (r,) = rec.window(sp.t0, sp.t1)
    assert r.name == "s" and r.ids == {"video": "cam0"}
    assert r.value == sp.seconds == sp.t1 - sp.t0


def test_span_is_recorded_when_its_block_raises():
    rec = Recorder()
    with pytest.raises(KeyError):
        with rec.span("s", profile=False):
            raise KeyError("x")
    assert rec.summary()["s"]["count"] == 1


def test_records_from_many_threads_are_all_kept():
    rec = Recorder(maxlen=1 << 16)
    n_threads, per = 16, 400
    start = threading.Barrier(n_threads)

    def work(tid):
        start.wait(timeout=30)
        for i in range(per):
            with rec.span("s", profile=False, tid=tid, i=i):
                pass
            rec.count("c", 1, tid=tid)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    held = rec.window(-1.0, time.monotonic())
    spans = {(r.ids["tid"], r.ids["i"]) for r in held if r.name == "s"}
    assert len(spans) == n_threads * per
    assert rec.summary()["c"]["count"] == n_threads * per


def test_module_functions_share_one_recorder():
    t0 = time.monotonic()
    with trace.span("tasm.test.module", profile=False):
        pass
    trace.count("tasm.test.count", 5)
    names = [r.name for r in trace.window(t0, time.monotonic())]
    assert "tasm.test.module" in names and "tasm.test.count" in names
    assert trace.summary()["tasm.test.count"]["max"] == 5


def test_profiler_copy_holds_leaf_spans_under_fixed_names(tmp_path):
    """A recording profiler shows each profiled span under its own name,
    its ids as event stats; a memory-only span never reaches it."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("tasm.test.outer", profile=False):
            with trace.span("tasm.test.leaf", batch=3):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = [e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("tasm.test.")]
    assert [e.name for e in events] == ["tasm.test.leaf"]
    assert ("batch", 3) in list(events[0].stats)

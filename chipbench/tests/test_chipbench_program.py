"""The readers of the program's own spans (``lib/program.py``), on
hand-made events and on one real session-store round trip."""
from __future__ import annotations

import types

import numpy as np
import pytest

from tiny import BENCH  # noqa: F401  (puts src/ and chipbench/ on the path)

from lib import bench as B
from lib import program as P
from lib.common import Recorder
from repro.core.obs import COMPILE, Event

NEW = ["kv_offload_fetch_ms", "kv_offload_checksum_ms", "kv_offload_store_ms",
       "kv_restore_read_ms", "kv_restore_checksum_ms",
       "kv_checksum_amplification", "window_compiles"]


def span(i, name, a, b, parent=0, **attrs):
    return Event("span", name, a, b, i, parent, attrs)


def offload(i, t, nbytes=100):
    """A 10 s ``kv.offload`` from ``t``: fetch 1 s, two checksums of 1 s
    (one nested in the store's write), store 4 s, 3 s untraced."""
    return [span(i + 1, "ser.to_host", t, t + 1, i, nbytes=nbytes),
            span(i + 2, "integrity.checksum", t + 1, t + 2, i,
                 nbytes=nbytes),
            span(i + 4, "integrity.checksum", t + 4, t + 5, i + 3,
                 nbytes=nbytes),
            span(i + 3, "obj.array_write", t + 3, t + 7, i + 5),
            span(i + 6, "obj.kv", t + 7.5, t + 8, i + 5),
            span(i + 5, "tx.commit", t + 3, t + 8, i),
            span(i, "kv.offload", t, t + 10, nbytes=nbytes)]


def test_buckets_and_untraced_sum_to_the_top_span():
    b = P.breakdown(offload(1, 0.0), 0, -1.0, 20.0, ("kv.offload",))
    assert b["n"] == 1 and b["total_s"] == 10
    assert (b["fetch"], b["checksum"], b["store"]) == (1, 2, 4)
    assert b["untraced"] == 3
    assert b["fetch"] + b["checksum"] + b["store"] + b["untraced"] == \
        pytest.approx(b["total_s"])
    assert (b["nbytes"], b["checksum_bytes"]) == (100, 200)


def test_a_child_is_charged_its_own_time_less_its_children():
    evs = [span(2, "obj.kv", 1, 4, 1), span(3, "integrity.checksum", 2, 3, 2),
           span(4, "obj.array_read", 5, 6, 1), span(1, "kv.restore", 0, 10)]
    b = P.breakdown(evs, 0, 0, 10, ("kv.restore",))
    assert (b["store"], b["checksum"], b["untraced"]) == (3, 1, 6)


def test_only_top_spans_inside_the_window_count():
    evs = offload(1, 0.0) + offload(11, 20.0) + offload(21, 35.0)
    b = P.breakdown(evs, 0, 15.0, 40.0, ("kv.offload",))
    assert b["n"] == 1 and b["total_s"] == 10 and b["store"] == 4
    assert P.breakdown(evs, 0, 15.0, 29.0, ("kv.offload",)) is None


def test_no_top_span_gives_none():
    assert P.breakdown(offload(1, 0.0), 0, 0, 20, ("kv.restore",)) is None
    assert P.breakdown([], 0, 0, 20, ("kv.offload",)) is None


def test_a_ring_that_dropped_inside_the_window_gives_none():
    evs = offload(1, 10.0)
    # dropped events ended before the oldest kept one (end 11): one that
    # may have ended after 5 could lie in a window from 5
    assert P.breakdown(evs, 3, 5.0, 30.0, ("kv.offload",)) is None
    assert P.breakdown(evs, 3, 12.0, 30.0, ("kv.offload",)) is None
    assert P.window_compiles(evs, 3, 5.0, 30.0) is None
    # every dropped event ended before the window opened
    late = offload(11, 20.0)
    assert P.breakdown(evs + late, 3, 19.0, 40.0,
                       ("kv.offload",))["n"] == 1


def tick(t, value=1):
    return Event("counter", COMPILE, t, t, 0, 0, {"value": value})


def test_window_compiles_counts_inside_the_window_once_listening():
    evs = [tick(1.0, 0), tick(2.0), tick(5.0), tick(6.0), tick(12.0)]
    assert P.window_compiles(evs, 0, 4.0, 10.0) == 2
    assert P.window_compiles(evs[:1], 0, 4.0, 10.0) == 0
    # listening began inside the window: compiles before it were missed
    assert P.window_compiles([tick(5.0, 0)], 0, 4.0, 10.0) is None


def test_readers_on_a_session_store_round_trip():
    """The new readers, found by name, read a real offload and restore
    inside a window, and their parts stay within the top spans."""
    from repro.core import Pool, Topology
    from repro.core.interfaces import DFS
    from repro.serve import KVCacheStore
    pool = Pool(Topology())
    store = KVCacheStore(DFS(pool.create_container("serve", oclass="S2")),
                         interface="daos-array", base="/kvcache")
    cache = {"k": np.ones((5 << 10, 1024), np.uint8),
             "v": np.zeros((3, 100), np.float32)}
    store.offload("s0", cache)          # set-up: outside the window
    rec = Recorder()
    with rec.span("window"):
        with rec.span("restore"):
            store.restore("s0")
        with rec.span("offload"):
            store.offload("s0", cache, step=1)
    data = types.SimpleNamespace(rec=rec)
    got = {m: B.metric_reader(m).read(data) for m in NEW}
    assert all(v is not None for v in got.values()), got
    off_ms = 1e3 * rec.durations("offload")[0]
    res_ms = 1e3 * rec.durations("restore")[0]
    parts = sum(got[m] for m in NEW[:3])
    assert 0 < parts <= off_ms
    assert 0 < got["kv_restore_read_ms"] + got["kv_restore_checksum_ms"] \
        <= res_ms
    assert 2.0 <= got["kv_checksum_amplification"] < 2.2
    assert got["window_compiles"] == 0


def test_readers_without_the_program_tracer_read_nothing(monkeypatch):
    """A checkout whose program has no tracer: every reader gives None."""
    import sys

    import repro.core
    monkeypatch.delattr(repro.core, "obs")
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    rec = Recorder()
    with rec.span("window"):
        pass
    data = types.SimpleNamespace(rec=rec)
    assert [B.metric_reader(m).read(data) for m in NEW] == [None] * len(NEW)


def test_program_idle_reads_a_trace_of_the_store(tmp_path):
    """``tools/program_idle.py`` on a CPU profiler trace of one offload
    inside a window: each ``repro:`` span in the trace matches the
    program's own record of it, and idle time between hand-made device
    operations goes to the innermost ``repro:`` span, else ``cb:`` span."""
    import jax

    from repro.core import Pool, Topology, obs
    from repro.core.interfaces import DFS
    from repro.serve import KVCacheStore
    from lib import trace as T
    tool = B.load_module(BENCH / "tools" / "program_idle.py")
    store = KVCacheStore(DFS(Pool(Topology()).create_container(
        "serve", oclass="S2")), interface="daos-array", base="/kvcache")
    cache = {"k": np.ones((2 << 10, 1024), np.uint8)}
    rec = Recorder(trace=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with rec.span("window"):
            with rec.span("offload"):
                store.offload("s0", cache)
            with rec.span("idle"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    spans = tool.host_spans(path)
    (tlo, thi), = [(a, b) for n, a, b in spans if n == "cb:window"]
    window, = [(a, b) for n, a, b in rec.spans if n == "window"]
    agree = tool.agreement(spans, obs.events(), (tlo, thi), window)
    assert agree["kv.offload"]["trace"] == agree["kv.offload"]["program"] \
        == 1
    assert agree["integrity.checksum"]["trace"] == \
        agree["integrity.checksum"]["program"] >= 2
    for a in agree.values():
        assert a["trace"] == a["program"] and a["max_diff_us"] < 1e3
    (_, olo, ohi), = [s for s in spans if s[0] == "repro:kv.offload"]
    dev = {"/device:TPU:0": {"ops": [("%f = x", tlo, tlo + 1),
                                     ("%g = y", thi - 1, thi)],
                             "modules": []}}
    s = tool.idle_by_span({"devices": dev}, spans)
    idle = dict(s["idle_gaps"])
    in_program = sum(v for n, v in idle.items() if n.startswith("repro:"))
    # the store's time goes to its own spans, hardly any to cb:offload
    assert in_program > 0.9 * (ohi - olo) / 1e9
    assert idle.get("cb:offload", 0) < 0.01 * in_program
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=1e-6)

"""The program's tracer (``repro.core.obs``): span nesting and parents,
per-thread stacks, the bounded ring, the compile counter, its place in
a profiler trace, and the spans of one session-store round trip."""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

from repro.core import EventQueue, Pool, Topology, obs
from repro.core.interfaces import DFS
from repro.core.multipart import MP_THRESHOLD
from repro.serve import KVCacheStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _new(before):
    """Spans recorded since ``before`` (an ``obs.events()`` list)."""
    seen = {e.id for e in before if e.kind == "span"}
    return [e for e in obs.events()
            if e.kind == "span" and e.id not in seen]


def test_nested_spans_record_parents_times_and_attributes():
    before = obs.events()
    with obs.span("outer", session="s1") as attrs:
        time.sleep(0.01)
        with obs.span("inner", nbytes=4):
            time.sleep(0.02)
        attrs["nbytes"] = 8
    inner, outer = _new(before)     # the ring holds spans as they end
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.parent == 0 and inner.parent == outer.id
    assert outer.attrs == {"session": "s1", "nbytes": 8}
    assert inner.attrs == {"nbytes": 4}
    assert outer.start <= inner.start < inner.end <= outer.end
    assert inner.end - inner.start >= 0.02
    own = (outer.end - outer.start) - (inner.end - inner.start)
    assert own >= 0.01


def test_span_as_decorator_and_on_error():
    @obs.span("decorated")
    def f(x):
        with obs.span("child"):
            return x + 1

    before = obs.events()
    assert f(1) == 2 and f(2) == 3
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError
    spans = _new(before)
    assert [e.name for e in spans] == ["child", "decorated"] * 2 + \
        ["failing"]
    assert spans[0].parent == spans[1].id and spans[2].parent == spans[3].id
    assert spans[1].id != spans[3].id and spans[4].parent == 0


def test_each_thread_has_its_own_stack():
    """Spans opened on another thread, an ``EventQueue`` worker included,
    are roots even while this thread has a span open."""
    before = obs.events()

    def work(name):
        with obs.span(name):
            with obs.span(name + ".child"):
                pass

    with obs.span("main"):
        t = threading.Thread(target=work, args=("thread",))
        t.start()
        t.join()
        with EventQueue(depth=2) as eq:
            eq.submit(work, "worker").wait()
    by = {e.name: e for e in _new(before)}
    assert by["thread"].parent == 0 and by["worker"].parent == 0
    assert by["thread.child"].parent == by["thread"].id
    assert by["worker.child"].parent == by["worker"].id
    assert by["main"].parent == 0


def test_ring_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(obs, "_ring", deque(maxlen=4))
    monkeypatch.setattr(obs, "_dropped", 0)
    for i in range(10):
        with obs.span(f"s{i}"):
            pass
    evs = obs.events()
    assert [e.name for e in evs] == ["s6", "s7", "s8", "s9"]
    assert obs.dropped() == 6
    assert all(a.end <= b.end for a, b in zip(evs, evs[1:]))


def test_ring_under_many_threads_loses_no_count(monkeypatch):
    """More threads than cores, switching often: every span is either
    kept or counted as dropped, kept ones in the order they ended, and
    each child's parent is a span of its own thread."""
    monkeypatch.setattr(obs, "_ring", deque(maxlen=1000))
    monkeypatch.setattr(obs, "_dropped", 0)
    n_threads, n_spans = 2 * (os.cpu_count() or 1) + 2, 150

    def work(i):
        for _ in range(n_spans):
            with obs.span(f"t{i}"):
                with obs.span(f"t{i}.child"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    evs = obs.events()
    assert len(evs) + obs.dropped() == 2 * n_threads * n_spans
    assert all(a.end <= b.end for a, b in zip(evs, evs[1:]))
    by_id = {e.id: e for e in evs}
    for e in evs:
        if e.name.endswith(".child") and e.parent in by_id:
            assert by_id[e.parent].name == e.name[:-len(".child")]


def test_compile_counter_counts_a_fresh_jit_once():
    import jax
    import jax.numpy as jnp

    with obs.span("listen"):        # registers the listener, jax loaded
        pass
    assert obs._listening
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 - 1.0)

    def compiles_during(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return [e for e in obs.events() if e.kind == "counter"
                and e.name == obs.COMPILE and e.start >= t0]

    first = compiles_during(lambda: f(x))
    assert sum(e.attrs["value"] for e in first) == 1
    assert first[0].attrs["program"]
    assert compiles_during(lambda: f(x)) == []


def test_import_of_repro_core_loads_no_jax():
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    code = ("import sys, repro.core\n"
            "from repro.core import obs\n"
            "with obs.span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "import jax\n"
            "with obs.span('y'):\n"
            "    pass\n"
            "ticks = [e for e in obs.events() if e.kind == 'counter']\n"
            "assert [e.attrs for e in ticks] == [{'value': 0}], ticks\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def _store():
    pool = Pool(Topology())
    dfs = DFS(pool.create_container("serve", oclass="S2"))
    return KVCacheStore(dfs, interface="daos-array", base="/kvcache")


def _cache():
    """One leaf above the multipart threshold (5 whole 1 MiB cells) and
    one of a partial cell."""
    rng = np.random.default_rng(0)
    return {"k": rng.integers(0, 255, MP_THRESHOLD + (1 << 20),
                              dtype=np.uint8).reshape(-1, 1024),
            "v": rng.standard_normal((3, 100)).astype(np.float32)}


def _tree(spans, top):
    kids = {}
    for e in spans:
        kids.setdefault(e.parent, []).append(e)

    def under(e):
        out = []
        for k in kids.get(e.id, []):
            out += [k] + under(k)
        return out
    return kids.get(top.id, []), under(top)


def test_session_store_round_trip_spans():
    store, cache = _store(), _cache()
    leaf_bytes = sum(a.nbytes for a in cache.values())
    before = obs.events()
    store.offload("s0", cache, step=1)
    restored = store.restore("s0")
    assert all(np.array_equal(restored[k], cache[k]) for k in cache)
    spans = _new(before)
    off, = [e for e in spans if e.name == "kv.offload"]
    res, = [e for e in spans if e.name == "kv.restore"]
    assert off.parent == res.parent == 0
    assert off.attrs == {"session": "s0", "nbytes": leaf_bytes}
    assert res.attrs == {"session": "s0", "nbytes": leaf_bytes}

    def through_leaves(top):
        """``top``'s children, each ``kv.leaf`` span replaced by its own
        children; and the ``kv.leaf`` spans."""
        kids, under = _tree(spans, top)
        leaves = [e for e in kids if e.name == "kv.leaf"]
        kids = [e for e in kids if e.name != "kv.leaf"] + [
            k for lf in leaves for k in _tree(spans, lf)[0]]
        assert sorted(lf.attrs["path"] for lf in leaves) == sorted(
            "/" + k for k in cache)
        assert sum(lf.attrs["nbytes"] for lf in leaves) == leaf_bytes
        return kids, under

    kids, under = through_leaves(off)
    names = {e.name for e in kids}
    assert {"ser.to_host", "integrity.checksum", "obj.submit",
            "tx.commit"} <= names
    assert sum(e.attrs["nbytes"] for e in kids
               if e.name == "ser.to_host") == leaf_bytes
    # each leaf checksummed once where it is serialized ...
    assert sum(e.attrs["nbytes"] for e in kids
               if e.name == "integrity.checksum") == leaf_bytes
    # ... and each cell again where the commit barrier stores it
    commit, = [e for e in kids if e.name == "tx.commit"]
    _, in_commit = _tree(spans, commit)
    writes = [e for e in in_commit if e.name == "obj.array_write"]
    assert len(writes) == 2
    cell_sums = [c for w in writes for c in _tree(spans, w)[0]
                 if c.name == "integrity.checksum"]
    assert sum(c.attrs["nbytes"] for c in cell_sums) == leaf_bytes
    assert any(e.name == "obj.kv" for e in in_commit)

    kids, under = through_leaves(res)
    assert sum(e.attrs["nbytes"] for e in kids
               if e.name == "integrity.checksum") == leaf_bytes
    assert {"obj.kv", "obj.multipart"} <= {e.name for e in kids}
    mp, = [e for e in kids if e.name == "obj.multipart"]
    assert {e.name for e in _tree(spans, mp)[1]} >= {
        "obj.array_read", "integrity.checksum"}
    # the big leaf through multipart, the small one read directly
    assert [e.name for e in kids if e.name.startswith("obj.array")] == \
        ["obj.array_read"]
    verified = [e for e in under if e.name == "integrity.checksum"
                and e.parent != res.id]
    assert sum(e.attrs["nbytes"] for e in verified) >= leaf_bytes


def test_profiler_trace_holds_the_spans_on_its_host_plane(tmp_path):
    """One CPU profiler trace: ``repro:kv.offload`` on the host plane with
    ``repro:integrity.checksum`` inside it."""
    import jax
    from jax.profiler import ProfileData

    store, cache = _store(), _cache()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        store.offload("s0", cache, step=1)
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(obs.PREFIX)]
    off = [e for e in events if e[0] == "repro:kv.offload"]
    assert len(off) == 1
    _, a, b = off[0]
    inside = [e for e in events if e[0] == "repro:integrity.checksum"
              and a <= e[1] and e[2] <= b]
    assert len(inside) >= 2

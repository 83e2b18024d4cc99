"""Randomized cross-policy coherence conformance harness.

Drives N cached client nodes — same-policy and mixed-policy fleets over
one shared file — through hundreds of seeded random op interleavings
(write / read / punch / fsync / tx-begin / tx-commit / tx-abort, with
page-aligned and page-straddling extents, and simulated time advancing
between ops so leases age) and checks EVERY read against an uncached
oracle:

* each byte a read returns must equal the current committed byte, the
  reading node's own unflushed (or tx-staged) byte, or — for a
  ``timeout``-policy node only — a byte that was still current at some
  instant within the last τ seconds (the staleness bound the lease
  protocol promises);
* ``broadcast`` and ``off`` nodes get no staleness budget at all: their
  reads must be current-or-own, byte for byte;
* after quiescing (flush everything, let every lease expire) all nodes
  must converge on identical current bytes.

The oracle never touches a cache: committed state is read straight from
the object layer at the committed epoch, and a history of
``(visible_at, bytes)`` snapshots — appended at every visibility event
(direct-I/O write, fsync flush, tx commit, punch) — defines the window a
stale byte may legally come from.

Shrink-friendly via ``hypothesis`` when it is installed; otherwise the
same core runs over a fixed-seed ``random`` matrix (deterministic: 50
seeds x 4 fleet configurations = 200 interleavings).
"""
from __future__ import annotations

import random

import numpy as np
import pytest

from repro.core import Pool, Topology
from repro.core.interfaces import DFS, make_interface

SIZE = 8 << 10            # file size: 8 pages of 1 KiB
PAGE = 1 << 10
TAU = 0.5
EPS = 1e-6
OPS = 32                  # ops per interleaving

#: fleet configurations: one coherence policy per client node.  The
#: ``-q8`` variants mount an async-capable interface with a deep
#: submission queue: their writers go through ``write_at_async``-queued
#: IODs that only reach the cache/engines at an ordering barrier — the
#: oracle tracks queued-but-unexecuted writes separately, so torn-offload
#: and commit-barrier guarantees are checked *under* queued submission.
FLEETS = {
    "all-broadcast": ("broadcast", "broadcast", "broadcast"),
    "all-timeout": ("timeout", "timeout", "timeout"),
    "all-off": ("off", "off", "off"),
    "mixed": ("broadcast", "timeout", "off"),
    "mixed-async": ("broadcast-q8", "timeout-q8", "off"),
}

MOUNTS = {
    "broadcast": "posix-cached:coherence=broadcast,page_kib=1,readahead=2",
    "timeout": f"posix-cached:timeout={TAU},page_kib=1,readahead=2",
    "off": "posix-cached:coherence=off",
    "broadcast-q8":
        "dfs-cached:coherence=broadcast,page_kib=1,readahead=2,qd=8",
    "timeout-q8": f"dfs-cached:timeout={TAU},page_kib=1,readahead=2,qd=8",
}


class _World:
    """One interleaving's cluster + oracle bookkeeping."""

    def __init__(self, policies: tuple, seed: int,
                 oclass: str = "S2") -> None:
        self.policies = policies
        self.rng = random.Random(seed)
        n = len(policies)
        self.pool = Pool(Topology(n_server_nodes=2, engines_per_node=2,
                                  n_client_nodes=n), materialize=True)
        cont = self.pool.create_container("conf", oclass=oclass)
        self.cont = cont
        dfs = DFS(cont)
        dfs.mkdir("/c")
        self.ifaces = [make_interface(MOUNTS[p], dfs) for p in policies]
        h0 = self.ifaces[0].create("/c/f", client_node=0, process=0)
        self.handles = [h0] + [
            self.ifaces[i].dup(h0, client_node=i, process=i)
            for i in range(1, n)]
        self.obj = h0.obj
        # oracle: committed-state history [(visible_at, bytes)] and one
        # unflushed-byte overlay per node ({offset: (value, tx)})
        self.history: list[tuple[float, bytes]] = []
        self.overlay: list[dict] = [dict() for _ in policies]
        # queued-but-unexecuted async writes, per node per handle:
        # {handle: [(off, ln, val), ...]} in submission order — invisible
        # to EVERYONE (the IOD hasn't reached even the writer's cache)
        # until an ordering barrier or window overflow retires it
        self.pending: list[dict] = [dict() for _ in policies]
        self.txs: list = [None] * n
        self.txh: list = [None] * n
        self.seq = 0
        self.checked_reads = 0
        self.stale_served = 0
        self.snapshot()

    # ---- oracle ----
    def _pol(self, node: int) -> str:
        """Base coherence policy of a node ("broadcast-q8" -> "broadcast")."""
        return self.policies[node].split("-")[0]

    @property
    def now(self) -> float:
        return self.pool.sim.clock.now

    def snapshot(self) -> None:
        cur = bytes(self.obj.read(0, SIZE,
                                  epoch=float(self.cont.committed_epoch)))
        if not self.history or self.history[-1][1] != cur:
            self.history.append((self.now, cur))

    def current(self) -> bytes:
        return self.history[-1][1]

    def allowed_values(self, node: int, b: int, base: bytes) -> set:
        """Legal values of byte ``b`` for a read by ``node`` right now.
        ``base`` is the node's fresh view: current committed bytes, or —
        under an open transaction — the snapshot-isolated view at the tx
        epoch (DAOS tx reads resolve records <= their epoch)."""
        ok = {base[b]}
        if b in self.overlay[node]:
            ok.add(self.overlay[node][b][0])
        if self._pol(node) == "timeout":
            # any value still current at some instant in (now - tau, now]:
            # snapshot i is current during [t_i, t_{i+1})
            horizon = self.now - TAU - EPS
            for i, (t_i, data) in enumerate(self.history):
                t_next = (self.history[i + 1][0]
                          if i + 1 < len(self.history) else float("inf"))
                if t_next > horizon:
                    ok.add(data[b])
        return ok

    def check_read(self, node: int, off: int, got: np.ndarray,
                   tx=None) -> None:
        """``tx`` is the transaction of the HANDLE the read went through
        (a node with an open tx may still read committed-view through its
        base handle)."""
        self.checked_reads += 1
        if tx is None:
            base = self.current()
        else:                        # snapshot isolation at the tx epoch
            base = bytes(self.obj.read(0, SIZE, epoch=float(tx.epoch)))
        raw = bytes(got)
        for j, v in enumerate(raw):
            b = off + j
            allowed = self.allowed_values(node, b, base)
            assert v in allowed, (
                f"node {node} ({self.policies[node]}) read byte {b} = {v}, "
                f"allowed {sorted(allowed)} at t={self.now:.3f} "
                f"(base={base[b]}, tx={'open' if tx else 'none'})")
            if v != base[b] and b not in self.overlay[node]:
                self.stale_served += 1

    # ---- op helpers ----
    def _extent(self) -> tuple[int, int]:
        """Page-aligned or straddling [offset, length)."""
        if self.rng.random() < 0.4:          # page-aligned
            off = self.rng.randrange(0, SIZE // PAGE) * PAGE
            ln = PAGE * self.rng.randint(1, 2)
        else:                                # straddling / unaligned
            off = self.rng.randrange(0, SIZE - 64)
            ln = self.rng.randint(1, 3 * PAGE)
        return off, min(ln, SIZE - off)

    def _handle(self, node: int):
        """The node's descriptor for this op: its tx handle while a tx is
        open — but sometimes the base (non-tx) handle anyway, modelling a
        second process on the node doing committed-view I/O concurrently
        with the transaction (this interleaving is what catches
        tx-snapshot/committed-view cache mixups)."""
        if self.txh[node] is not None and self.rng.random() >= 0.3:
            return self.txh[node]
        return self.handles[node]

    def _apply_write(self, node: int, h, off: int, ln: int,
                     val: int) -> None:
        """Oracle effects of one write that has now actually executed
        through handle ``h`` (sync, or a retired queued IOD)."""
        if h.tx is not None:
            for b in range(off, off + ln):
                self.overlay[node][b] = (val, h.tx)
        elif self._pol(node) == "off":
            self.snapshot()                  # direct I/O: visible at once
        else:
            for b in range(off, off + ln):
                self.overlay[node][b] = (val, None)

    def _sync_pending(self, node: int, h) -> None:
        """Queued writes the submission window has already forced out
        (all of them, at qd=1 mounts) become oracle-visible: the handle's
        ``queued`` count says how many are still unexecuted."""
        lst = self.pending[node].get(h)
        while lst and len(lst) > h.queued:
            off, ln, val = lst.pop(0)
            self._apply_write(node, h, off, ln, val)

    def _drain_pending(self, node: int, h) -> None:
        """A sync op on ``h`` is an ordering barrier: retire the queue
        and fold every queued write into the oracle before the op runs."""
        lst = self.pending[node].pop(h, None)
        if not lst:
            return
        h.flush_queue()
        for off, ln, val in lst:
            self._apply_write(node, h, off, ln, val)
        self.snapshot()

    def op_write(self, node: int) -> None:
        off, ln = self._extent()
        self.seq += 1
        val = self.seq % 250 + 1             # never 0 (hole byte)
        h = self._handle(node)
        self._drain_pending(node, h)
        h.write_at(off, bytes([val]) * ln)
        self._apply_write(node, h, off, ln, val)

    def op_write_async(self, node: int) -> None:
        """A queued write: submitted now, executed at a barrier / window
        overflow / tx commit — or torn away by a tx abort."""
        off, ln = self._extent()
        self.seq += 1
        val = self.seq % 250 + 1
        h = self._handle(node)
        h.write_at_async(off, bytes([val]) * ln)
        self.pending[node].setdefault(h, []).append((off, ln, val))
        self._sync_pending(node, h)

    def op_read(self, node: int) -> None:
        off, ln = self._extent()
        h = self._handle(node)
        self._drain_pending(node, h)
        got = h.read_at(off, ln)
        self.check_read(node, off, got, tx=h.tx)

    def op_fsync(self, node: int) -> None:
        h = self._handle(node)
        self._drain_pending(node, h)
        h.fsync()
        if h.tx is None:
            # non-tx dirty bytes are on the engines now
            self.overlay[node] = {b: v for b, v in
                                  self.overlay[node].items()
                                  if v[1] is not None}
            self.snapshot()
        # tx-staged flushes land at the (still invisible) tx epoch

    def op_tx_begin(self, node: int) -> None:
        if self.txs[node] is not None:
            return
        tx = self.cont.tx_begin()
        self.txs[node] = tx
        self.txh[node] = self.ifaces[node].dup(
            self.handles[node], client_node=node, process=node, tx=tx)

    def op_tx_commit(self, node: int) -> None:
        tx = self.txs[node]
        if tx is None:
            return
        # the commit barrier drains the tx handle's submission queue:
        # still-queued writes land at the tx epoch and commit with it —
        # the post-commit snapshot() below picks their bytes up
        self.pending[node].pop(self.txh[node], None)
        tx.commit()
        self.overlay[node] = {b: v for b, v in self.overlay[node].items()
                              if v[1] is not tx}
        self.txs[node] = self.txh[node] = None
        self.snapshot()

    def op_tx_abort(self, node: int) -> None:
        tx = self.txs[node]
        if tx is None:
            return
        # abort discards queued-but-unexecuted IODs — their bytes never
        # reach any cache or engine (torn-offload under queued submission)
        self.pending[node].pop(self.txh[node], None)
        tx.abort()
        self.overlay[node] = {b: v for b, v in self.overlay[node].items()
                              if v[1] is not tx}
        self.txs[node] = self.txh[node] = None
        self.snapshot()

    def op_punch(self, node: int) -> None:
        self.obj.punch()
        for i in range(len(self.policies)):
            self.overlay[i] = {}
        self.snapshot()

    # ---- driver ----
    def op_table(self) -> list[tuple]:
        # write weight splits 6 sync + 4 async: the totals (and so the
        # cumulative-weight boundaries of every OTHER op) match the
        # pre-async harness, keeping the fixed-seed matrix's coverage —
        # including its known stale-serve interleavings — intact
        return [(self.op_write, 6), (self.op_write_async, 4),
                (self.op_read, 12), (self.op_fsync, 5),
                (self.op_tx_begin, 3), (self.op_tx_commit, 2),
                (self.op_tx_abort, 1), (self.op_punch, 1)]

    def pre_quiesce(self) -> None:
        """Hook for subclasses that must repair the cluster first."""

    def run(self) -> None:
        ops = self.op_table()
        funcs = [f for f, _ in ops]
        weights = [w for _, w in ops]
        for _ in range(OPS):
            self.pool.sim.clock.advance(self.rng.uniform(0.0, 0.3))
            node = self.rng.randrange(len(self.policies))
            self.rng.choices(funcs, weights)[0](node)
            # visibility can change on ANY op in the epoch model (e.g. a
            # tx's staged records leak into the committed view once the
            # auto-epoch watermark passes the tx epoch), so the oracle
            # re-snapshots after every op (dedup keeps history small)
            self.snapshot()
        self.pre_quiesce()
        self.quiesce()

    def quiesce(self) -> None:
        """Drain: close transactions, flush everything, let every lease
        expire — then every node must read identical current bytes."""
        for node in range(len(self.policies)):
            if self.txs[node] is not None:
                if self.rng.random() < 0.5:
                    self.op_tx_commit(node)
                else:
                    self.op_tx_abort(node)
            self._drain_pending(node, self.handles[node])
            self.op_fsync(node)
        self.pool.sim.clock.advance(TAU + 0.1)   # expire all leases
        cur = self.current()
        for node, h in enumerate(self.handles):
            got = bytes(h.read_at(0, SIZE))
            assert got == cur, (
                f"node {node} ({self.policies[node]}) diverged after "
                "quiesce")


def run_interleaving(fleet: str, seed: int) -> _World:
    w = _World(FLEETS[fleet], seed)
    w.run()
    return w


# ---------------- deterministic fixed-seed matrix (200 runs) -------------
@pytest.mark.parametrize("fleet", sorted(FLEETS))
@pytest.mark.parametrize("seed", range(50))
def test_conformance(fleet, seed):
    w = run_interleaving(fleet, seed)
    assert w.checked_reads > 0


#: interleavings random draws once failed, pinned by cause: a write at a
#: newer epoch in a cell the tx wrote hides its bytes after commit (a
#: record is a whole cell); a commit or the watermark passing an open tx
#: changes what readers see without moving any token; an abort takes away
#: records the watermark had already shown; an entry retagged before its
#: tx commits, or filled before it joins the tx, does not hold the tx's
#: view.  (all-broadcast 982526257 pins the replay running mid-flush.)
COUNTEREXAMPLES = [
    ("all-broadcast", 276600181), ("all-broadcast", 24520513),
    ("all-broadcast", 62534619), ("all-broadcast", 1282502805),
    ("all-broadcast", 560025634), ("all-broadcast", 363698845),
    ("all-broadcast", 982526257), ("all-broadcast", 1334828420),
    ("all-broadcast", 1440983678), ("all-broadcast", 267798643),
    ("all-timeout", 1634354142), ("mixed", 981658422),
    ("mixed", 1628980321), ("mixed", 1775344344),
    ("mixed-async", 2070305828), ("mixed-async", 1909209475),
]


@pytest.mark.parametrize("fleet,seed", COUNTEREXAMPLES)
def test_conformance_counterexample(fleet, seed):
    w = run_interleaving(fleet, seed)
    assert w.checked_reads > 0


def test_staleness_is_actually_exercised():
    """The harness must not pass vacuously: across the fixed-seed matrix,
    timeout fleets really do serve (legally) stale bytes sometimes, and
    plenty of reads are checked.  If a future change makes staleness
    unobservable here, the op mix needs re-tuning, not the bound."""
    reads = stale = 0
    for seed in range(50):
        w = run_interleaving("all-timeout", seed)
        reads += w.checked_reads
        stale += w.stale_served
        if stale and reads > 50:
            break
    assert reads > 50
    assert stale > 0


def test_broadcast_and_off_never_serve_stale():
    for seed in range(12):
        for fleet in ("all-broadcast", "all-off"):
            w = run_interleaving(fleet, seed)
            assert w.stale_served == 0, (fleet, seed)


# ---------------- async-KV-writer interleavings --------------------------
class _KVWorld:
    """Seeded interleavings of batched (queued) and serial KV writers
    over one shared KVObject, checked against a value oracle.

    This is the metadata-plane sibling of the file harness above, with its
    OWN op table (the file matrix's cumulative-weight boundaries stay
    untouched).  The oracle mirrors the container's epoch machine rather
    than keeping a last-write-wins dict, because visibility is decided by
    epochs, not wall-clock execution order: every non-tx put is stamped at
    the moment it *executes* (window overflow, an explicit flush, or a tx
    commit barrier), while a tx's records are all stamped with the epoch
    allocated at tx *begin*.  A reader sees the highest stamp at or below
    the committed watermark, so a committed tx loses any dkey that a
    non-tx writer touched after the tx began — and because the watermark
    is a max, a tx's executed records leak into the committed view as soon
    as any later auto-epoch put lands, even before commit.  An abort
    punches the tx epoch: the queued tail is discarded, the executed
    prefix vanishes.  Execution order is deterministic — per-queue
    submission order, folded into the oracle in the order batches retire
    ops — so the expected value of every dkey is exact, not a set.
    """

    DKEYS = 6

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.pool = Pool(Topology(n_server_nodes=2, engines_per_node=2,
                                  n_client_nodes=2), materialize=True)
        cont = self.pool.create_container("kvconf", oclass="S2")
        self.cont = cont
        dfs = DFS(cont)
        self.iface = make_interface("dfs:qd=4", dfs)
        self.kv = cont.open_kv("kv:conf", oclass="RP_2G1")
        # oracle mirror of the engines' version store: dkey -> {stamp: val}
        # (stamps share one counter with tx-begin, like the real allocator)
        self.records: dict[str, dict[int, bytes]] = {}
        self.stamp = 0
        self.watermark = 0
        # open non-tx batches: [(batch, unfolded [(dkey, val), ...])]
        self.batches: list = []
        # one optional open tx batch: (tx, tx_stamp, batch, unfolded)
        self.txb = None
        self.seq = 0
        self.checked = 0

    def _val(self) -> bytes:
        self.seq += 1
        return b"%06d" % self.seq

    def _auto(self) -> int:
        """Mirror ``auto_epoch``: allocate a stamp and advance the
        watermark past it (independent puts are immediately visible)."""
        self.stamp += 1
        self.watermark = max(self.watermark, self.stamp)
        return self.stamp

    def _visible(self, dkey: str) -> bytes | None:
        """Mirror ``fetch`` at the committed watermark: newest stamp at
        or below it wins."""
        versions = self.records.get(dkey, {})
        live = [s for s in versions if s <= self.watermark]
        return versions[max(live)] if live else None

    def _fold(self, entry) -> None:
        """Fold executed (retired) puts of one batch into the oracle —
        everything the queue no longer holds has hit the engines.  Each
        one consumed an auto epoch at execution time."""
        batch, unfolded = entry
        while unfolded and len(unfolded) > batch.inflight:
            dkey, val = unfolded.pop(0)
            self.records.setdefault(dkey, {})[self._auto()] = val

    def _fold_tx(self) -> None:
        """Executed tx puts reach the engines stamped with the epoch fixed
        at tx begin (no allocation at execution time)."""
        _tx, tx_stamp, batch, unfolded = self.txb
        while unfolded and len(unfolded) > batch.inflight:
            dkey, val = unfolded.pop(0)
            self.records.setdefault(dkey, {})[tx_stamp] = val

    def op_batch_put(self) -> None:
        if not self.batches or (len(self.batches) < 2
                                and self.rng.random() < 0.4):
            self.batches.append(
                (self.iface.kv_batch(self.kv), []))
        entry = self.rng.choice(self.batches)
        dkey = f"d{self.rng.randrange(self.DKEYS)}"
        val = self._val()
        entry[0].put(dkey, "a", val)
        entry[1].append((dkey, val))
        self._fold(entry)

    def op_serial_put(self) -> None:
        dkey = f"d{self.rng.randrange(self.DKEYS)}"
        val = self._val()
        self.kv.put(dkey, "a", val, ctx=self.iface.make_ctx())
        self.records.setdefault(dkey, {})[self._auto()] = val

    def op_flush(self) -> None:
        if not self.batches:
            return
        entry = self.batches.pop(self.rng.randrange(len(self.batches)))
        entry[0].flush()
        for dkey, val in entry[1]:
            self.records.setdefault(dkey, {})[self._auto()] = val

    def op_read(self) -> None:
        dkey = f"d{self.rng.randrange(self.DKEYS)}"
        self.checked += 1
        try:
            got = bytes(self.kv.get(dkey, "a"))
        except Exception:
            got = None
        assert got == self._visible(dkey), (
            f"dkey {dkey}: read {got!r}, oracle "
            f"{self._visible(dkey)!r}")

    def op_tx_begin(self) -> None:
        if self.txb is not None:
            return
        tx = self.cont.tx_begin()
        self.stamp += 1                  # alloc_epoch: watermark untouched
        self.txb = (tx, self.stamp, self.iface.kv_batch(self.kv, tx=tx), [])

    def op_tx_put(self) -> None:
        if self.txb is None:
            return
        dkey = f"d{self.rng.randrange(self.DKEYS)}"
        val = self._val()
        self.txb[2].put(dkey, "a", val)
        self.txb[3].append((dkey, val))
        self._fold_tx()

    def op_tx_commit(self) -> None:
        if self.txb is None:
            return
        tx, tx_stamp, _batch, unfolded = self.txb
        tx.commit()                      # barrier drains the batch
        for dkey, val in unfolded:
            self.records.setdefault(dkey, {})[tx_stamp] = val
        self.watermark = max(self.watermark, tx_stamp)
        self.txb = None

    def op_tx_abort(self) -> None:
        if self.txb is None:
            return
        tx, tx_stamp, _batch, _unfolded = self.txb
        tx.abort()                       # queued tail discarded, epoch
        for versions in self.records.values():   # punched everywhere
            versions.pop(tx_stamp, None)
        self.txb = None

    def run(self, n_ops: int = 40) -> None:
        ops = [(self.op_batch_put, 10), (self.op_serial_put, 6),
               (self.op_read, 12), (self.op_flush, 5),
               (self.op_tx_begin, 3), (self.op_tx_put, 4),
               (self.op_tx_commit, 2), (self.op_tx_abort, 1)]
        funcs = [f for f, _ in ops]
        weights = [w for _, w in ops]
        for _ in range(n_ops):
            self.rng.choices(funcs, weights)[0]()
        # quiesce: resolve the tx, flush every open batch, re-check all
        if self.txb is not None:
            if self.rng.random() < 0.5:
                self.op_tx_commit()
            else:
                self.op_tx_abort()
        while self.batches:
            self.op_flush()
        for i in range(self.DKEYS):
            dkey = f"d{i}"
            try:
                got = bytes(self.kv.get(dkey, "a"))
            except Exception:
                got = None
            assert got == self._visible(dkey), dkey
            self.checked += 1


@pytest.mark.parametrize("seed", range(30))
def test_async_kv_writer_conformance(seed):
    w = _KVWorld(seed)
    w.run()
    assert w.checked > 0


# ---------------- failure-schedule interleavings (claim F4) ---------------
class _FTWorld(_World):
    """The same oracle, with engine failure / costed rebuild / fenced
    restore injected mid-interleaving.

    The shared file is RP_2G1-protected so every byte survives a single
    engine failure: reads during the degraded window reconstruct from the
    surviving replica and must STILL be byte-exact against the oracle
    (current, own-unflushed, or inside the timeout window — a failure
    never widens the staleness budget).  Recovery is the documented
    sequence — ``rebuild()`` (full record-history replay, including
    still-open tx epochs, onto a replacement) then ``restore_engine``
    (empty, version counters reset, every cache fenced keep-dirty) — and
    torn-offload guarantees must hold across it: a tx aborted after a
    rebuild replayed its staged records must leave no trace anywhere.
    """

    def __init__(self, policies: tuple, seed: int) -> None:
        super().__init__(policies, seed, oclass="RP_2G1")
        self.dead_engine: int | None = None
        self.fail_cycles = 0

    def op_fail(self, node: int) -> None:
        if self.dead_engine is not None:
            return
        eid = self.rng.choice(self.pool.live_engine_ids())
        self.pool.fail_engine(eid)
        self.dead_engine = eid
        self.fail_cycles += 1

    def op_recover(self, node: int) -> None:
        if self.dead_engine is None:
            return
        self.pool.rebuild()
        self.pool.restore_engine(self.dead_engine)
        self.dead_engine = None

    def op_table(self) -> list[tuple]:
        return super().op_table() + [(self.op_fail, 3),
                                     (self.op_recover, 3)]

    def pre_quiesce(self) -> None:
        self.op_recover(0)


@pytest.mark.parametrize("seed", range(50))
def test_failure_schedule_conformance(seed):
    w = _FTWorld(FLEETS["mixed"], seed)
    w.run()
    assert w.checked_reads > 0


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("fleet", ["all-timeout", "mixed-async"])
def test_failure_schedule_conformance_other_fleets(fleet, seed):
    w = _FTWorld(FLEETS[fleet], seed)
    w.run()
    assert w.checked_reads > 0


def test_failures_are_actually_exercised():
    """The F4 matrix must not pass vacuously: across the fixed seeds the
    schedule really does kill engines mid-interleaving."""
    cycles = 0
    for seed in range(50):
        w = _FTWorld(FLEETS["mixed"], seed)
        w.run()
        cycles += w.fail_cycles
        if cycles >= 10:
            break
    assert cycles >= 10


def test_restore_without_fence_would_serve_stale():
    """Satellite pin: ``restore_engine`` must reset the engine's version
    counters and fence attached caches.  A client that cached pages (and
    their token sum) while an engine was dead would otherwise revalidate
    against a restored-empty engine whose preserved counters re-create
    the remembered sum — and keep serving bytes the rebuild moved away."""
    w = _FTWorld(FLEETS["all-timeout"], seed=7)
    # deterministic mini-schedule instead of the random op table
    w.op_write(0)
    w.op_fsync(0)
    w.op_fail(0)
    w.op_read(1)            # degraded read fills node 1's cache
    w.op_recover(0)         # rebuild + fenced restore
    w.op_write(0)           # new bytes land post-recovery
    w.op_fsync(0)
    w.snapshot()
    w.pool.sim.clock.advance(TAU + 0.1)
    w.op_read(1)            # must see the post-recovery bytes
    w.quiesce()


# ---------------- hypothesis front-end (shrinks when available) ----------
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(fleet=st.sampled_from(sorted(FLEETS)),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_conformance_hypothesis(fleet, seed):
        run_interleaving(fleet, seed)
except ImportError:                  # fixed-seed matrix above still runs
    pass

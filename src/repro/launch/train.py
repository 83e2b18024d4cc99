"""End-to-end training driver.

Wires every substrate together: config -> model -> object-store data
pipeline -> jit'd train step -> async transactional checkpointing -> failure
detection/restart.  The CPU tests drive the reduced (smoke) configs;
``--no-smoke`` takes a registry config at its published widths, vocabulary
included, as ``chip_smoke.py`` does on one chip.

``--kill-at-step N`` simulates a mid-run crash (storage engine failure +
worker loss) and demonstrates the recovery path: detector fires -> pool
rebuild -> restore_latest -> elastic replan -> training resumes.  Used by
examples/train_restart.py and the integration tests.  Recovery handles the
store's own failures (I/O errors: engine loss, data loss, a checkpoint
that cannot be read) and the injected one; any other error, a device
error from the train step among them, ends the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..configs import get_arch, smoke_variant
from ..core import Pool, Topology
from ..core.interfaces import DFS
from ..ckpt import Checkpointer, CheckpointManager
from ..data import ObjectStoreDataset, Prefetcher, synthetic_corpus, \
    write_corpus
from ..ft import FailureDetector, replan_data_parallel
from ..models import init_model
from ..train import make_train_step, opt_init
from .compile_cache import enable_compile_cache


class InjectedNodeFailure(Exception):
    """The ``--kill-at-step`` crash: an engine and a worker go down."""


# what the recovery path handles: the injected crash and the store's own
# errors, which are IOErrors (EngineFailedError, DataLossError,
# CheckpointError, ...); JAX's runtime errors are RuntimeErrors
RECOVERABLE = (InjectedNodeFailure, IOError)


def build_world(args, vocab: int):
    pool = Pool(Topology(n_server_nodes=args.servers,
                         engines_per_node=2))
    cont = pool.create_container("train", oclass=args.oclass)
    dfs = DFS(cont)
    corpus = synthetic_corpus(args.corpus_tokens, vocab)
    write_corpus(dfs, corpus, shard_tokens=args.shard_tokens,
                 interface=args.interface, oclass=args.oclass)
    ds = ObjectStoreDataset(dfs, interface=args.interface)
    # checkpoints use a *protected* object class (paper's RP_*/EC_* classes):
    # losing an engine must never lose training state.
    ckpt = Checkpointer(dfs, interface=args.interface,
                        oclass=args.ckpt_oclass,
                        layout=args.ckpt_layout, n_writers=args.servers)
    mgr = CheckpointManager(ckpt, save_every=args.ckpt_every, keep_n=2)
    return pool, dfs, ds, mgr


def model_config(args):
    """The registry config (or its smoke variant); its own vocabulary
    unless ``--vocab`` overrides it."""
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if args.vocab:
        cfg = dataclasses.replace(cfg, vocab_size=args.vocab)
    return dataclasses.replace(cfg, grad_compression=args.grad_compression)


def run(args) -> dict:
    """Train ``args.steps`` steps.  Returns the run's summary (printed),
    plus its per-step ``losses`` and ``step_s`` (host seconds of each step,
    loss fetched), the host seconds each save held the loop
    (``save_stall_s``: the device-to-host snapshot) and the final wait for
    the queued saves (``drain_s``), the final device ``state``
    ({"params", "opt"}) and the checkpoint ``manager``."""
    cfg = model_config(args)
    pool, dfs, ds, mgr = build_world(args, cfg.vocab_size)
    det = FailureDetector(pool, n_workers=args.workers)

    key = jax.random.PRNGKey(args.seed)
    params = init_model(key, cfg)
    opt_state = opt_init(cfg.optimizer, params)
    t0 = time.perf_counter()
    step_fn = jax.jit(make_train_step(cfg)).lower(
        params, opt_state,
        {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)},
    ).compile()
    compile_s = time.perf_counter() - t0

    pf = Prefetcher(ds, depth=4)
    batches = pf.batches(args.batch, args.seq, seed=args.seed)

    losses, step_s, save_stall_s = [], [], []
    step = 0
    restarts = 0
    t0 = time.time()
    while step < args.steps:
        try:
            if args.kill_at_step and step == args.kill_at_step and \
                    restarts == 0:
                # simulate: one storage engine dies AND a worker is lost
                pool.fail_engine(sorted(pool.engines)[0])
                det.fail_worker(args.workers - 1, step)
                raise InjectedNodeFailure("injected node failure")

            batch = next(batches)
            ts = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            step_s.append(time.perf_counter() - ts)
            ts = time.perf_counter()
            if mgr.maybe_save(step, {"params": params, "opt": opt_state},
                              extra_meta={"step": step}, async_=True):
                save_stall_s.append(time.perf_counter() - ts)
            step += 1
        except StopIteration:
            break
        except RECOVERABLE:
            # ---- recovery path ----
            restarts += 1
            events = det.poll(step)
            pool.rebuild()
            dp, per_replica = replan_data_parallel(
                args.batch, det.n_alive_workers or 1)
            restored_step, tree = mgr.restore_latest(
                {"params": params, "opt": opt_state}, pool=pool)
            params, opt_state = tree["params"], tree["opt"]
            params = jax.tree.map(jnp.asarray, params)
            opt_state = jax.tree.map(jnp.asarray, opt_state)
            step = restored_step + 1
            pf = Prefetcher(ds, depth=4)
            batches = pf.batches(args.batch, args.seq, seed=args.seed + step)
            print(f"[recovery] events={[(ev.kind, ev.ident) for ev in events]}"
                  f" restored step {restored_step}, dp={dp}, "
                  f"per_replica={per_replica}")
    ts = time.perf_counter()
    mgr.drain()
    drain_s = time.perf_counter() - ts
    out = {
        "final_loss": losses[-1] if losses else None,
        "first_loss": losses[0] if losses else None,
        "steps": step, "restarts": restarts,
        "stragglers_skipped": pf.skipped,
        "compile_s": compile_s,
        "wall_s": time.time() - t0,
        "sim_io_s": pool.sim.clock.now,
    }
    print({k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in out.items()})
    return {**out, "losses": losses, "step_s": step_s,
            "save_stall_s": save_stall_s, "drain_s": drain_s,
            "state": {"params": params, "opt": opt_state}, "manager": mgr}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=None,
                    help="override the vocabulary (default: the config's "
                         "own; the smoke variant's is 256)")
    ap.add_argument("--interface", default="dfs")
    ap.add_argument("--oclass", default="S2")
    ap.add_argument("--ckpt-oclass", default="RP_2GX")
    ap.add_argument("--ckpt-layout", default="sharded",
                    choices=["sharded", "shared"])
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--kill-at-step", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--corpus-tokens", type=int, default=300_000)
    ap.add_argument("--shard-tokens", type=int, default=32768)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main() -> None:
    args = parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()

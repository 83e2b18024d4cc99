#!/usr/bin/env python3
"""Bring-up check: the serve and train paths, end to end, on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in this one process, through the normal entry points, at the
registry's published widths with weights and data made from ``--seed``:

* serve — h2o-danube-1.8b: four 2048-token prompts prefilled, 16 greedy
  decode steps, the session cache offloaded to a ``daos-array`` mount of
  the modelled store, restored and uploaded again; one more decode step
  from the original cache and one from the restored cache must give
  bit-identical logits.
* train — mamba2-370m through ``repro.launch.train.run --no-smoke`` (batch
  8, seq 2048, 4 steps, AdamW): every loss finite, the async checkpoint
  of the last step lands, and ``restore_latest`` gives back the device
  state byte for byte.

Each phase prints its compile and host times and the bytes it moved; the
times are host-clock readings of a single run, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``.  Exits non-zero,
printing no result, when JAX's first device is not a TPU or a phase fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SERVE_ARCH = "h2o-danube-1.8b"
SERVE_BATCH, PROMPT_LEN, DECODE_STEPS = 4, 2048, 16
TRAIN_ARGV = ["--arch", "mamba2-370m", "--no-smoke", "--batch", "8",
              "--seq", "2048", "--steps", "4", "--ckpt-every", "3"]


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def same_bytes(a, b) -> bool:
    """Two pytrees hold the same leaves, byte for byte."""
    import jax
    import numpy as np
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.tobytes() != y.tobytes():
            return False
    return True


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.core import Pool, Topology
    from repro.core.interfaces import DFS
    from repro.models import init_model
    from repro.serve import KVCacheStore, make_decode_step, make_prefill_step

    cfg = get_arch(SERVE_ARCH)
    B, S, T = SERVE_BATCH, PROMPT_LEN, DECODE_STEPS
    k_init, k_prompt = jax.random.split(jax.random.PRNGKey(seed))
    params = init_model(k_init, cfg)
    prompts = jax.random.randint(k_prompt, (B, S), 0, cfg.vocab_size,
                                 jnp.int32)
    jax.block_until_ready((params, prompts))
    log("serve", arch=SERVE_ARCH, params_bytes=tree_bytes(params),
        batch=B, prompt=S)

    t0 = time.perf_counter()
    prefill = jax.jit(make_prefill_step(cfg, pad_to=S + T + 1)).lower(
        params, {"tokens": prompts}).compile()
    prefill_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompts})
    jax.block_until_ready((logits, cache))
    prefill_s = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    pos0 = jnp.asarray(S, jnp.int32)
    t0 = time.perf_counter()
    decode = jax.jit(make_decode_step(cfg)).lower(
        params, cache, tok, pos0).compile()
    decode_compile_s = time.perf_counter() - t0
    step_s = []
    for t in range(T):
        t0 = time.perf_counter()
        tok, lg, cache = decode(params, cache, tok,
                                jnp.asarray(S + t, jnp.int32))
        jax.block_until_ready((tok, lg, cache))
        step_s.append(time.perf_counter() - t0)
    if not bool(jnp.isfinite(lg.astype(jnp.float32)).all()):
        raise AssertionError("decode logits are not finite")
    log("serve", prefill_compile_s=prefill_compile_s,
        decode_compile_s=decode_compile_s, prefill_s=prefill_s,
        decode_ms_per_token_median=1e3 * statistics.median(step_s),
        decode_ms_per_token_first=1e3 * step_s[0], decode_steps=T)

    # the session cache round trip through the modelled store
    pool = Pool(Topology())
    dfs = DFS(pool.create_container("serve", oclass="S2"))
    store = KVCacheStore(dfs, interface="daos-array", base="/kvcache")
    nbytes = tree_bytes(cache)
    t0 = time.perf_counter()
    store.offload("sess0", cache, step=S + T)
    offload_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    restored = store.restore("sess0")
    restore_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    cache2 = jax.block_until_ready(jax.device_put(restored))
    upload_s = time.perf_counter() - t0
    log("serve", cache_bytes=nbytes, offload_s=offload_s,
        restore_s=restore_s, upload_s=upload_s)
    if not same_bytes(cache, cache2):
        raise AssertionError("restored session cache differs from the "
                             "offloaded one")

    pos = jnp.asarray(S + T, jnp.int32)
    _, lg1, _ = decode(params, cache, tok, pos)
    _, lg2, _ = decode(params, cache2, tok, pos)
    lg1, lg2 = np.asarray(lg1), np.asarray(lg2)
    if lg1.shape != (B, 1, cfg.padded_vocab()):
        raise AssertionError(f"decode logits shape {lg1.shape}")
    if lg1.tobytes() != lg2.tobytes():
        raise AssertionError("decode from the restored cache differs")
    log("serve", restored_decode="bit-identical", logits_shape=lg1.shape)


def train_phase(seed: int) -> None:
    import math

    from repro.launch import train

    args = train.parse_args(TRAIN_ARGV + ["--seed", str(seed)])
    out = train.run(args)
    losses, mgr, state = out["losses"], out["manager"], out["state"]
    if len(losses) != args.steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses {losses}")
    log("train", arch=args.arch, batch=args.batch, seq=args.seq,
        steps=out["steps"], compile_s=out["compile_s"],
        step_s=out["step_s"], save_stall_s=out["save_stall_s"],
        drain_s=out["drain_s"], losses=losses)

    t0 = time.perf_counter()
    step, restored = mgr.restore_latest(state)
    restore_s = time.perf_counter() - t0
    log("train", saved_steps=mgr.saved_steps, restored_step=step,
        state_bytes=tree_bytes(state), restore_s=restore_s)
    if step != args.steps - 1:
        raise AssertionError(f"latest checkpoint is step {step}, "
                             f"not the last step {args.steps - 1}")
    if not same_bytes(state, restored):
        raise AssertionError("restored checkpoint differs from the device "
                             "state")
    log("train", restored_state="byte-identical")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log("setup", device_kind=dev.device_kind, count=len(jax.devices()),
        compile_cache=enable_compile_cache())

    for name, phase in (("serve", serve_phase), ("train", train_phase)):
        t0 = time.perf_counter()
        phase(args.seed)
        log(name, phase_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

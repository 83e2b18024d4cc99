"""Returning chat sessions, restored from the store into decode.

Set-up prefills each session's history with the program's prefill step
and offloads its cache through ``KVCacheStore``.  The window is an open
loop: returns fall due at fixed intervals (``rate_per_s``), served one
at a time, sessions in the traffic file's ``order`` (a cycle that
alternates large and small sessions) from a starting point the seed
draws; the seed also draws the history and user tokens and the weights.
A turn
restores the session's cache, uploads it, feeds the turn's user tokens
through the decode step (the program has no prefill onto an existing
cache), decodes the greedy answer and offloads the cache again.

End-to-end: ``return_ttft_p50_ms`` (due time to the first answer token
on the host, over every return due in the window) and ``tpot_p95_ms``
(every gap between two answer tokens reaching the host, each token
fetched as a streaming server sends it, over every return).

Correct: after the window, the reference runs over a seeded sample of
finished turns (the turn with the longest context always among them) and
reads, at every answer position, how far the served token's logit lies
below the reference's best.  The widest such gap is held to its limit.
"""
from __future__ import annotations

import math
import time

import numpy as np

from lib import weights
from lib.common import (NO_READING, BenchError, Check, free_device_memory,
                        jax_key, memory_peak_bytes, np_rng, quantile)


def plan(traffic: dict, seconds: float, seed: int) -> dict:
    """Due times, session order and turn counts of one run.  The order is
    the traffic file's cycle, started where the seed says: every seed
    serves the same sessions in another order, and the cycle keeps large
    and small sessions alternating, so the queueing is alike."""
    n = len(traffic["sessions"])
    n_due = max(1, math.ceil(seconds * traffic["rate_per_s"]))
    cycle = traffic["order"]
    if sorted(cycle) != list(range(n)):
        raise BenchError(f"order {cycle} is not a permutation of the "
                         f"{n} sessions")
    k = int(np_rng(seed, 4).integers(n))
    order = cycle[k:] + cycle[:k]
    turns = [0] * n
    seq = []
    for r in range(n_due):
        s = order[r % n]
        seq.append((s, turns[s]))
        turns[s] += 1
    return {"interval": 1.0 / traffic["rate_per_s"], "returns": seq,
            "turns": turns}


def check_fit(traffic: dict, turns: list, window: int) -> None:
    """A cache shorter than the model's window must hold every token the
    run gives its session; one as long as the window is a ring."""
    per_turn = traffic["user_tokens"] + traffic["answer_tokens"]
    for i, s in enumerate(traffic["sessions"]):
        need = s["history"] + turns[i] * per_turn
        if s["history"] > s["slots"]:
            raise BenchError(f"session {i}: history {s['history']} > "
                             f"slots {s['slots']}")
        if s["slots"] != window and need > s["slots"]:
            raise BenchError(f"session {i} needs {need} slots, has "
                             f"{s['slots']} (raise slots or lower the rate)")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import repro.models as models
    import repro.serve as serve
    from repro.core import Pool, Topology
    from repro.core.interfaces import DFS

    tr, cfg, rec = ctx.traffic, ctx.cfg, ctx.rec
    U, A = tr["user_tokens"], tr["answer_tokens"]
    V = cfg.vocab_size
    window = cfg.swa_window or 10 ** 9
    p = plan(tr, ctx.seconds, ctx.seed)
    check_fit(tr, p["turns"], window)
    sessions = tr["sessions"]
    rng = np_rng(ctx.seed, 1)
    hist = [rng.integers(0, V, s["history"], dtype=np.int32)
            for s in sessions]
    user = [[rng.integers(0, V, U, dtype=np.int32) for _ in range(t)]
            for t in p["turns"]]

    with rec.span("weights"):
        spec = jax.eval_shape(
            lambda: models.init_model(jax.random.PRNGKey(0), cfg))
        layout = weights.layout_of(spec)
        params = weights.make(jax_key(ctx.seed, 1), layout,
                              ctx.config["program"]["family"],
                              ctx.cell.bench_dir)
        jax.block_until_ready(params)

    pool = Pool(Topology())
    dfs = DFS(pool.create_container("serve", oclass=tr["oclass"]))
    store = serve.KVCacheStore(dfs, interface=tr["interface"],
                               base="/kvcache")

    sds = jax.ShapeDtypeStruct
    tok_spec, pos_spec = sds((1, 1), jnp.int32), sds((), jnp.int32)
    with rec.span("compile"):
        decode = {}
        for slots in sorted({s["slots"] for s in sessions}):
            cache_spec = models.cache_spec(cfg, slots, 1)
            decode[slots] = jax.jit(serve.make_decode_step(cfg)).lower(
                params, cache_spec, tok_spec, pos_spec).compile()
        prefill = {}
        for s in sessions:
            key = (s["history"], s["slots"])
            if key not in prefill:
                prefill[key] = jax.jit(serve.make_prefill_step(
                    cfg, pad_to=s["slots"])).lower(
                    params, {"tokens": sds((1, s["history"]), jnp.int32)}
                ).compile()

    with rec.span("build_sessions"):
        for i, s in enumerate(sessions):
            _, cache = prefill[(s["history"], s["slots"])](
                params, {"tokens": hist[i][None]})
            # warm each decode program once on a cache of its shape
            t, _, _ = decode[s["slots"]](params, cache,
                                         np.zeros((1, 1), np.int32),
                                         np.int32(s["history"]))
            jax.block_until_ready(t)
            store.offload(f"s{i}", cache, step=s["history"])
            del cache
    user_dev = [[[jax.device_put(np.array([[x]], np.int32)) for x in u]
                 for u in us] for us in user]
    setup_s = time.perf_counter() - ctx.t_start

    pos = [s["history"] for s in sessions]
    answers = [[] for _ in sessions]
    ttft, tpot, finished, timeline = [], [], [], []
    decode_steps, lost = {}, set()
    with ctx.window():
        t0 = time.perf_counter()
        for r, (i, turn) in enumerate(p["returns"]):
            due = t0 + r * p["interval"]
            wait = due - time.perf_counter()
            if wait > 0:
                with rec.span("idle"):
                    time.sleep(wait)
            if i in lost:       # a session whose turn failed is gone
                continue
            s = sessions[i]
            step = decode[s["slots"]]
            started = time.perf_counter()
            try:
                with rec.span("restore"):
                    tree = store.restore(f"s{i}")
                with rec.span("upload"):
                    cache = jax.block_until_ready(jax.device_put(tree))
                del tree
                with rec.span("decode"):
                    for u in user_dev[i][turn]:
                        tok, _, cache = step(params, cache, u,
                                             np.int32(pos[i]))
                        pos[i] += 1
                    out = [np.asarray(tok)]
                    t_first = time.perf_counter()
                    ttft.append(t_first - due)
                    timeline.append((due - t0, started - t0, t_first - due))
                    marks = [t_first]
                    for k in range(A):
                        tok, _, cache = step(params, cache, tok,
                                             np.int32(pos[i]))
                        pos[i] += 1
                        if k < A - 1:   # an answer token, sent on
                            out.append(np.asarray(tok))
                            marks.append(time.perf_counter())
                    tpot.extend(np.diff(marks))
                    decode_steps[s["slots"]] = \
                        decode_steps.get(s["slots"], 0) + U + A
                with rec.span("offload"):
                    store.offload(f"s{i}", cache, step=pos[i])
                del cache
            except IOError:     # the store's errors: the return fails
                lost.add(i)
                continue
            answers[i].append(np.concatenate([t.reshape(-1) for t in out]))
            finished.append((i, turn))
        window_s = time.perf_counter() - t0
    peak = memory_peak_bytes(ctx.devices)

    metrics = {"setup_s": setup_s}
    if ttft:
        metrics["return_ttft_p50_ms"] = 1e3 * quantile(ttft, 0.5)
    if tpot:
        metrics["tpot_p95_ms"] = 1e3 * quantile(tpot, 0.95)

    del params, store, pool, dfs, decode, prefill, user_dev
    free_device_memory()
    with rec.span("reference"):
        gap = answer_gap(ctx, layout, hist, user, answers, finished, U, A)
    checks = [Check("answer_gap_max", gap, ctx.limits["answer_gap_max"])]
    control = []
    if ctx.control:
        control.append(Check("answer_gap_max", answer_gap(
            ctx, layout, hist, user, answers, finished, U, A, lowp=True),
            ctx.limits["answer_gap_max"]))
    return {
        "control": control,
        "metrics": metrics, "attempted": len(p["returns"]),
        "failed": len(p["returns"]) - len(finished),
        "checks": checks, "memory_peak_bytes": peak,
        "extras": {"decode_steps_by_slots": decode_steps,
                   "window_s": window_s, "returns": timeline},
    }


def turn_tokens(hist, user, answers, i, turn, U, A):
    """Every token of session ``i`` up to the end of ``turn``, and the
    positions whose logits chose that turn's answers."""
    seq = [hist[i]]
    for t in range(turn + 1):
        seq += [user[i][t], answers[i][t]]
    seq = np.concatenate(seq)
    end_user = hist[i].size + turn * (U + A) + U
    positions = np.arange(end_user - 1, end_user - 1 + A)
    return seq, positions


def sample_turns(finished, hist, U, A, n, seed):
    """A seeded sample of finished turns, the longest context first."""
    if not finished:
        return []
    length = {f: hist[f[0]].size + (f[1] + 1) * (U + A) for f in finished}
    longest = max(finished, key=lambda f: (length[f], -f[0]))
    rest = [f for f in finished if f != longest]
    pick = np_rng(seed, 2).permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[j] for j in sorted(pick)]


def answer_gap(ctx, layout, hist, user, answers, finished, U, A,
               lowp: bool = False) -> float:
    """Widest gap, over the sampled answers, between the reference's best
    logit and the logit of the token served (``lowp``: of the token the
    float8 control puts first)."""
    import jax
    ref = ctx.reference
    if tuple(ref.layout(ctx.config)) != tuple(layout):
        raise BenchError("the reference's weight table differs from the "
                         "program's")
    tr = ctx.traffic
    picks = sample_turns(finished, hist, U, A, tr["check_turns"], ctx.seed)
    if not picks:
        return NO_READING
    params = weights.make(jax_key(ctx.seed, 1), layout,
                          ctx.config["program"]["family"],
                          ctx.cell.bench_dir)
    worst = 0.0
    for i, turn in picks:
        seq, positions = turn_tokens(hist, user, answers, i, turn, U, A)
        lg = np.asarray(ref.logits_at(params, ctx.config, seq, positions,
                                      tr["check_pad"]))
        if lowp:
            lo = np.asarray(ref.logits_at(params, ctx.config, seq,
                                          positions, tr["check_pad"],
                                          lowp=True))
            chosen = lo.argmax(-1)
        else:
            chosen = answers[i][turn]
        gaps = lg.max(-1) - lg[np.arange(A), chosen]
        worst = max(worst, float(gaps.max()))
    del params
    jax.clear_caches()
    return worst

"""The session-return driver at a tiny size on the CPU: a sound run is
correct, the float8 control and each planted fault are not."""
from __future__ import annotations

import numpy as np
import pytest

from tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_session_return_runs_and_is_correct(dtype):
    result, checks = run_tiny("danube-session-return", param_dtype=dtype)
    assert result["correct"] is True
    assert result["attempted"] == 8 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "return_ttft_p50_ms",
                                      "tpot_p95_ms"}
    assert [c.name for c in checks] == ["answer_gap_max"]
    if dtype == "float32":
        assert checks[0].value < 1e-3


def _altered_decode(real):
    """The program's decode step with its answer token altered."""
    def make(cfg, greedy=True):
        step = real(cfg, greedy)

        def broken(params, cache, tokens, pos):
            tok, logits, cache = step(params, cache, tokens, pos)
            return (tok + 1) % cfg.vocab_size, logits, cache
        return broken
    return make


def _stale_decode(real):
    """The program's decode step returning the cache it was given."""
    def make(cfg, greedy=True):
        step = real(cfg, greedy)

        def broken(params, cache, tokens, pos):
            tok, logits, _ = step(params, cache, tokens, pos)
            return tok, logits, cache
        return broken
    return make


@pytest.mark.parametrize("fault", [_altered_decode, _stale_decode],
                         ids=["answer-altered", "state-unchanged"])
def test_session_return_fault_is_not_correct(monkeypatch, fault):
    import repro.serve as serve
    monkeypatch.setattr(serve, "make_decode_step",
                        fault(serve.make_decode_step))
    result, checks = run_tiny("danube-session-return")
    assert result["correct"] is False
    assert checks[0].value > checks[0].limit


def test_float8_control_reads_wider_than_the_program():
    """The control (the reference in float8, in the program's place) is
    held to the limit by the same checks as the program, and comes out
    not correct where the program is correct.  At this size the program
    is float32 and reads no gap, so the limit sits between the two."""
    import time

    import jax

    import run as R
    cell, cfg = tiny_cell("danube-session-return", check_turns=8)
    cell.limits = {"answer_gap_max": 0.01}
    out = {}
    result, checks = R.run_cell(cell, 7, 0.2, False, jax.devices()[:1],
                                time.perf_counter(), cfg=cfg, out=out)
    assert result["correct"] is True
    assert [c.name for c in out["control"]] == ["answer_gap_max"]
    assert out["control"][0].limit == checks[0].limit
    assert not all(c.ok for c in out["control"])

"""The harness: what it refuses, what its result line holds, and that a
new configuration, traffic mix or metric is found by name alone."""
from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, ROOT, run_tiny

from lib import bench as B
from lib import counts, peaks
from lib.common import BenchError, emit

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "breakdown", "checks"}


def _cli(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_cli_refuses_a_cpu():
    r = _cli(ROOT, "--workload", "danube-session-return", "--seed",
             str(2 ** 31 + 5), "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, "--workload", "danube-session-return", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_workload_is_refused():
    with pytest.raises(BenchError, match="no workload"):
        B.cell(B.load_benchmark(), "no-such-cell")


def test_result_line_has_only_the_contract_keys(capsys):
    result, checks = run_tiny("danube-session-return")
    emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) <= CONTRACT_KEYS
    assert CONTRACT_KEYS - {"breakdown"} <= set(line)
    assert list(line)[-1] == "checks"
    assert line["checks"]["answer_gap_max"].keys() == {"value", "limit"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert err.strip().splitlines()[-1].startswith("check answer_gap_max")


def test_benchmark_names_every_file_it_needs():
    bench = B.load_benchmark()
    for w in bench["workloads"]:
        cell = B.cell(bench, w["name"])
        assert cell.driver.run
        assert cell.reference.layout(cell.config)
        for m in cell.per_layer:
            assert B.metric_reader(m["name"]).read


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    """A dummy traffic mix and per-layer metric, added as files and
    entries only, are found by the names in BENCHMARK.json."""
    for d in ("traffic", "metrics", "limits"):
        (tmp_path / d).mkdir()
    shutil.copytree(BENCH / "families", tmp_path / "families")
    (tmp_path / "limits" / "dummy-cell.json").write_text(
        json.dumps({"answer_gap_max": 0.5}))
    base = json.loads((BENCH / "traffic" / "session_return.json").read_text())
    base["rate_per_s"] = 0.1
    (tmp_path / "traffic" / "dummy_mix.json").write_text(json.dumps(base))
    (tmp_path / "metrics" / "dummy.metric.py").write_text(
        "def read(data):\n    return data['x'] * 2\n")
    bench = B.load_benchmark()
    bench["workloads"].append({"name": "dummy-cell",
                               "config": "h2o-danube-1.8b",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "dummy"})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "tpot_p95_ms",
                               "workloads": ["dummy-cell"]})
    for m in bench["end_to_end"]:
        if "danube-session-return" in m.get("workloads", []):
            m["workloads"].append("dummy-cell")
    cell = B.cell(bench, "dummy-cell", bench_dir=tmp_path)
    assert cell.traffic["rate_per_s"] == 0.1
    assert cell.bench_dir == tmp_path   # its family is loaded from there
    assert cell.driver.__name__.endswith("session_return")
    assert [m["name"] for m in cell.per_layer] == ["dummy.metric"]
    assert {m["name"] for m in cell.end_to_end} == {
        "return_ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    assert B.metric_reader("dummy.metric", tmp_path).read({"x": 4}) == 8


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError, match="no published peaks"):
        peaks.peaks("cpu")


CONFIGS = {c["name"]: c["file"] for c in B.load_benchmark()["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_agree_with_the_program(name):
    """Parameter counts and cache bytes from the configuration file alone,
    by the family file it names, agree with the program's own shapes at
    the published sizes.  Every configuration in ``BENCHMARK.json`` is a
    case, so every family file that one names is checked."""
    import jax
    import numpy as np

    import repro.models as models
    config = B.read_json(ROOT / CONFIGS[name])
    cfg = B.program_config(config)
    c = counts.counts_for(config)
    spec = jax.eval_shape(lambda: models.init_model(jax.random.PRNGKey(0),
                                                    cfg))
    assert c.n_params == models.param_count(spec)
    for slots in (1792, 4096):
        cache = models.cache_spec(cfg, slots, 1)
        nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
        assert c.cache_bytes(slots) == nbytes
    assert c.decode_bytes(4096) > c.cache_bytes(4096) + 3.4e9


def test_stdout_result_is_one_json_line(capsys):
    from lib.common import Check
    emit({"correct": True, "attempted": 1, "failed": 0, "metrics": {},
          "device": {}}, [Check("x", 0.5, 1.0)])
    out = capsys.readouterr().out
    assert len(out.strip().splitlines()) == 1
    assert json.load(io.StringIO(out))["checks"]["x"]["limit"] == 1.0

"""Dry-run integration: the production-mesh lower+compile path, exercised
end-to-end in a subprocess (512 host devices must be configured before jax
init, so this cannot run in-process with the rest of the suite)."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("args,expect_dom", [
    (["--arch", "mamba2-370m", "--shape", "decode_32k"], None),
    (["--arch", "chatglm3-6b", "--shape", "decode_32k", "--multi-pod"],
     None),
])
def test_dryrun_cell_compiles(args, expect_dom, tmp_path):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/tmp", "JAX_PLATFORMS": "cpu"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and not k.startswith("XLA")})
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args,
         "--tag", "testrun"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "saved" in out.stdout
    mesh = "2x16x16" if "--multi-pod" in args else "16x16"
    art = (ROOT / "artifacts" / "dryrun" /
           f"{args[1]}__{args[3]}__{mesh}__testrun.json")
    res = json.loads(art.read_text())
    r = res["roofline"]
    assert r["compute_s"] >= 0 and r["memory_s"] > 0
    assert res["per_device"]["hlo_flops"] > 0
    assert res["n_devices"] == (512 if "--multi-pod" in args else 256)


def test_host_mesh_has_auto_axes():
    """Meshes are built with Auto axes: under jax.make_mesh's default
    (Explicit) the embedding gather's output spec repeats an axis."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_sharding_rules_divisibility():
    """Every param leaf's sharded dims must divide by the mesh axis size
    for every arch (the invariant the dry-run relies on)."""
    import numpy as np
    from repro.configs import ARCHS
    from repro.models import param_shapes

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    from repro.launch.mesh import ShardingRules, axis_size
    for name, cfg in ARCHS.items():
        shapes = param_shapes(cfg, tp_pad=16)
        rules = ShardingRules(cfg, FakeMesh())
        specs = rules.param_specs(shapes)
        flat_s, _ = __import__("jax").tree.flatten(shapes)
        flat_p, _ = __import__("jax").tree.flatten(
            specs, is_leaf=lambda x: hasattr(x, "index"))
        for s, spec in zip(flat_s, flat_p):
            for dim, ax in zip(s.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([FakeMesh.shape[a] for a in axes]))
                assert dim % size == 0, (name, s.shape, tuple(spec))

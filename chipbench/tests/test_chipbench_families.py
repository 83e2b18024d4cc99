"""Architecture families: ``chipbench/families/<family>.py`` found by the
name a configuration gives, its counts and weight rules used, and the
``dense`` family giving what the harness gave before families were
files."""
from __future__ import annotations

import hashlib
import json
import re

import numpy as np
import pytest

from tiny import BENCH, tiny_cell

from lib import bench as B
from lib import counts, weights
from lib.common import BenchError, jax_key

DANUBE = BENCH / "configs" / "h2o-danube-1.8b.json"

# h2o-danube-1.8b, computed by the dense counts before they moved into
# families/dense.py: slots -> (cache_bytes, decode_flops, decode_bytes)
DENSE_KNOWN = {1792: (110100480, 3938713600, 3608729600),
               2560: (157286400, 4127457280, 3655915520),
               4096: (251658240, 4504944640, 3750287360)}


def test_dense_params_match_the_known_counts():
    c = counts.counts_for(B.read_json(DANUBE))
    assert (c.n_params, c.matmul_params) == (1831201280, 1749155840)


@pytest.mark.parametrize("slots", sorted(DENSE_KNOWN))
def test_dense_decode_counts_match_the_known_counts(slots):
    c = counts.counts_for(B.read_json(DANUBE))
    assert (c.cache_bytes(slots), c.decode_flops(slots),
            c.decode_bytes(slots)) == DENSE_KNOWN[slots]


def fingerprint(tree) -> str:
    """sha256 over every leaf's path, type, shape and bytes, by path."""
    flat = weights.flatten(tree)
    h = hashlib.sha256()
    for path in sorted(flat):
        a = np.asarray(flat[path])
        for part in (path, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def tiny_layout(dtype: str) -> tuple:
    import jax

    import repro.models as models
    _, cfg = tiny_cell("danube-session-return", dtype)
    return weights.layout_of(jax.eval_shape(
        lambda: models.init_model(jax.random.PRNGKey(0), cfg)))


# the tiny danube layout's weights for seed 2**31 + 5, stream 1, made by
# the leaf rules before a family could add its own
DENSE_FINGERPRINT = {
    "float32":
    "98db4d3a157921ab66ba2be05eb7cf51b58876c8ab293be74552a0c120c9978e",
    "bfloat16":
    "56f608e3de669c58dc18276a3e840118192f2be3206fafecef9c9f9bb08cf22e"}


@pytest.mark.parametrize("dtype", sorted(DENSE_FINGERPRINT))
def test_dense_weights_match_the_known_fingerprint(dtype):
    params = weights.make(jax_key(2 ** 31 + 5, 1), tiny_layout(dtype),
                          "dense")
    assert fingerprint(params) == DENSE_FINGERPRINT[dtype]


FAMILY = '''
import jax.numpy as jnp


class Counts:
    n_params, matmul_params = 7, 5

    def __init__(self, config):
        self.config = config

    def cache_bytes(self, slots, batch=1):
        return 11 * slots * batch

    def decode_flops(self, slots, batch=1):
        return 13 * slots * batch

    def decode_bytes(self, slots, batch=1):
        return 17 * slots * batch


def _twos(key, shape):
    return jnp.full(shape, 2.0, jnp.float32)


LEAF_RULES = {"gate_bias": _twos}
'''


def test_a_new_family_is_found_by_name(tmp_path):
    """A family file that no other file names, written into a benchmark
    directory of its own, gives the counts and the weight rule; a leaf
    its rules do not name gets the default rule."""
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "toy_family.py").write_text(FAMILY)
    config = {"name": "toy", "program": {"family": "toy_family"}}
    c = counts.counts_for(config, tmp_path)
    assert (c.n_params, c.matmul_params) == (7, 5)
    assert (c.cache_bytes(2, 3), c.decode_flops(2), c.decode_bytes(4)) \
        == (66, 26, 68)
    assert c.config is config
    layout = (("/blocks/gate_bias", (2, 8), "float32"),
              ("/blocks/norm1", (2, 8), "float32"),
              ("/blocks/w", (2, 8, 4), "bfloat16"))
    key = jax_key(2 ** 31 + 9, 1)
    got = weights.flatten(weights.make(key, layout, "toy_family", tmp_path))
    dense = weights.flatten(weights.make(key, layout, "dense"))
    assert np.all(np.asarray(got["/blocks/gate_bias"]) == 2.0)
    assert np.asarray(dense["/blocks/gate_bias"]).std() > 0.1
    assert np.all(np.asarray(got["/blocks/norm1"]) == 1.0)
    assert got["/blocks/w"].dtype == dense["/blocks/w"].dtype
    np.testing.assert_array_equal(np.asarray(got["/blocks/w"]),
                                  np.asarray(dense["/blocks/w"]))


def test_a_missing_family_is_refused_by_the_cell(tmp_path):
    """A configuration naming a family with no file is refused when its
    cell is found, with the path looked for, before anything is built."""
    config = B.read_json(DANUBE)
    config["program"]["family"] = "no_such_family"
    conf_file = tmp_path / "config.json"
    conf_file.write_text(json.dumps(config))
    bench = B.load_benchmark()
    conf, = [c for c in bench["configs"] if c["name"] == config["name"]]
    conf["file"] = str(conf_file)       # absolute: read in place of ROOT's
    want = BENCH / "families" / "no_such_family.py"
    with pytest.raises(BenchError, match=re.escape(str(want))):
        B.cell(bench, "danube-session-return")
    with pytest.raises(BenchError, match=re.escape(str(want))):
        counts.counts_for(config)

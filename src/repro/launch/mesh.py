"""Production meshes and sharding rules.

Mesh: (data=16, model=16) single pod / (pod=2, data=16, model=16) across two
pods.  The `pod` axis composes with `data` as the outer data-parallel axis;
`model` carries TP (heads / ffn / vocab / experts).

Param sharding policy (per leaf, by name + trailing-dims rule):
  * TP dim over 'model' wherever the natural TP dim divides by 16
    (q-heads are pre-padded in the model so they always divide);
  * FSDP: the d_model-sized dim over ('pod','data') — params AND optimizer
    state are fully sharded, which is what lets arctic-480b fit;
  * small leaves (norm scales, biases, conv taps) replicated.
Stacked layer pytrees carry a leading L dim — specs are right-aligned.

IMPORTANT: importing this module never touches jax device state; meshes are
built inside functions only (the dry-run sets XLA_FLAGS before any jax
import).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    """``jax.make_mesh`` with Auto axes.  Its default is Explicit axes,
    under which the sharding rules' specs become part of every array's
    type and the embedding gather's output spec names one axis twice
    (``DuplicateSpecError``); Auto axes leave propagation to GSPMD, which
    is what these rules were written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist, as a 1x1 (data, model) mesh per device count
    — used by smoke tests and the CPU examples."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def mesh_axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


def dp_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None


def axis_size(mesh: Mesh, *names: str) -> int:
    return int(np.prod([mesh.shape[n] for n in names if n in mesh.shape]))


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


class ShardingRules:
    """Builds PartitionSpecs for params / optimizer state / batches / caches
    of one (cfg, mesh) pair."""

    def __init__(self, cfg, mesh: Mesh, *,
                 fsdp: bool = True, tp_attention: bool = True,
                 tp_seq_decode: bool = True) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.tp = mesh.shape.get("model", 1)
        self.dp = dp_axes(mesh)
        self.dp_size = axis_size(mesh, "pod", "data")
        self.fsdp = fsdp
        self.tp_attention = tp_attention
        self.tp_seq_decode = tp_seq_decode

    # -------------- param rules --------------
    def _leaf_spec(self, path: str, shape: tuple) -> P:
        cfg, tp = self.cfg, self.tp
        dpx = self.dp if self.fsdp else None
        nd = len(shape)

        def right_align(*spec):
            pad = (None,) * (nd - len(spec))
            return P(*(pad + tuple(spec)))

        last = shape[-1] if nd else 0
        second = shape[-2] if nd >= 2 else 0

        if nd <= 1 or min(shape[-2:]) == 1:
            return P()  # scalars, norm scales, biases, conv taps

        name = path.split("/")[-1]
        # --- embeddings ---
        if name == "tok":
            return right_align("model" if _div(second, tp) else None,
                               dpx if _div(last, self.dp_size) else None)
        if name == "head":
            return right_align(dpx if _div(second, self.dp_size) else None,
                               "model" if _div(last, tp) else None)
        # --- MoE experts (E, d, ff) / (E, ff, d) ---
        if "moe" in path and name in ("w_gate", "w_up"):
            return right_align("model" if _div(shape[-3], tp) else None,
                               dpx if _div(second, self.dp_size) else None,
                               None)
        if "moe" in path and name == "w_down":
            return right_align("model" if _div(shape[-3], tp) else None,
                               None,
                               dpx if _div(last, self.dp_size) else None)
        if name == "router":
            return right_align(dpx if _div(second, self.dp_size) else None,
                               None)
        # --- projections with contraction on d_model (d, out) ---
        if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_in",
                    "w_branch", "w_gate_branch", "w_r", "w_i"):
            tp_ok = self.tp_attention if name in ("wq", "wk", "wv") else True
            return right_align(
                dpx if _div(second, self.dp_size) else None,
                "model" if (tp_ok and _div(last, tp)) else None)
        # --- projections back to d_model (out, d) ---
        if name in ("wo", "w_down", "w_out"):
            tp_ok = self.tp_attention if name == "wo" else True
            return right_align(
                "model" if (tp_ok and _div(second, tp)) else None,
                dpx if _div(last, self.dp_size) else None)
        if name == "conv":
            return right_align(None, None)
        return P()  # default: replicated

    def param_specs(self, shapes_tree):
        flat, tree = jax.tree_util.tree_flatten_with_path(shapes_tree)

        def path_str(p):
            return "/".join(str(getattr(k, "key", k)) for k in p)

        specs = [self._leaf_spec(path_str(p), tuple(s.shape))
                 for p, s in flat]
        return jax.tree.unflatten(tree, specs)

    def opt_specs(self, opt_shapes, param_specs_tree):
        """Optimizer state mirrors param specs; factored Adafactor leaves
        drop the reduced axis."""
        pflat, _ = jax.tree_util.tree_flatten_with_path(param_specs_tree)
        pspec_by_path = {"/".join(str(getattr(k, "key", k)) for k in p): s
                         for p, s in pflat}

        oflat, otree = jax.tree_util.tree_flatten_with_path(opt_shapes)
        out = []
        for path, leaf in oflat:
            keys = [str(getattr(k, "key", k)) for k in path]
            slot, rest = keys[0], "/".join(keys[1:])
            base = pspec_by_path.get(rest)
            if base is None or slot == "count":
                out.append(P())
                continue
            spec = tuple(base)
            nd = len(leaf.shape)
            if slot == "vr":      # reduced last axis
                spec = spec[:-1] if len(spec) == nd + 1 else spec
            elif slot == "vc":    # reduced second-to-last axis
                spec = (spec[:-2] + spec[-1:]) if len(spec) == nd + 1 else spec
            if len(spec) != nd:
                spec = (None,) * nd
            # drop shardings that no longer divide
            fixed = []
            for dim, ax in zip(leaf.shape, spec):
                sz = (axis_size(self.mesh, *(ax if isinstance(ax, tuple)
                                             else (ax,)))
                      if ax else 1)
                fixed.append(ax if ax and dim % sz == 0 else None)
            out.append(P(*fixed))
        return jax.tree.unflatten(otree, out)

    # -------------- batch / cache rules --------------
    def batch_specs(self, batch_shapes):
        def spec(path, s):
            if s.shape == ():
                return P()
            if not _div(s.shape[0], self.dp_size):
                return P(*((None,) * len(s.shape)))
            return P(self.dp, *((None,) * (len(s.shape) - 1)))

        flat, tree = jax.tree_util.tree_flatten_with_path(batch_shapes)
        return jax.tree.unflatten(tree, [spec(p, s) for p, s in flat])

    def cache_specs(self, cache_shapes):
        """Cache leaves are layer-stacked: (L, B, S, Hkv, D) etc.
        KV heads shard over 'model' when divisible, else the sequence dim
        does (flash-decode style: softmax reduces over the sharded axis)."""
        cfg, tp = self.cfg, self.tp

        def spec(path, s):
            keys = "/".join(str(getattr(k, "key", k)) for k in path)
            nd = len(s.shape)
            batch_ok = _div(s.shape[1], self.dp_size) if nd >= 2 else False
            bspec = self.dp if batch_ok else None
            if keys.endswith(("k", "v")) and nd == 5:
                L, B, S, H, D = s.shape
                if _div(H, tp):
                    return P(None, bspec, None, "model", None)
                if self.tp_seq_decode and _div(S, tp):
                    return P(None, bspec, "model", None, None)
                return P(None, bspec, None, None, None)
            if keys.endswith("state") and nd == 5:   # ssm (L,B,H,N,P)
                L, B, H, N, Pd = s.shape
                return P(None, bspec, "model" if _div(H, tp) else None,
                         None, None)
            if keys.endswith(("rec_h", "rec_conv")):
                w = s.shape[-1]
                return P(*((None,) * (nd - 1)),
                         "model" if _div(w, tp) else None)
            if keys.endswith("conv") and nd == 4:     # ssm conv state
                return P(None, bspec, None, None)
            return P(*((None,) * nd))

        flat, tree = jax.tree_util.tree_flatten_with_path(cache_shapes)
        return jax.tree.unflatten(tree, [spec(p, s) for p, s in flat])

    # -------------- helpers --------------
    def named(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax import: jax locks the device count on first init.
# Everything below may import jax freely.
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import pathlib       # noqa: E402

import jax                                   # noqa: E402
import numpy as np                           # noqa: E402

from ..configs import ARCHS, SHAPES, get_arch, shape_applicable  # noqa: E402
from ..models import input_specs, param_shapes                   # noqa: E402
from ..serve import make_decode_step, make_prefill_step          # noqa: E402
from ..train import make_train_step, opt_state_shapes            # noqa: E402
from .hlo_cost import analyze as hlo_analyze                     # noqa: E402
from .mesh import ShardingRules, axis_size, make_production_mesh  # noqa: E402

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# v5e-class hardware constants (per brief)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per ICI link


def _sds_with_sharding(shapes_tree, shardings_tree):
    return jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shapes_tree, shardings_tree)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode counts one
    token per sequence; prefill counts forward only (2 N D)."""
    n = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_cell(arch: str, shape_name: str, mesh, *, fsdp=True,
               remat=None, overrides: dict | None = None,
               extra: dict | None = None):
    import dataclasses
    cfg = get_arch(arch)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(v) if cur is not None else v
        cfg = dataclasses.replace(cfg, **typed)
    shape = SHAPES[shape_name]
    rules = ShardingRules(cfg, mesh, fsdp=fsdp, **(extra or {}))
    tp = mesh.shape.get("model", 1)
    dp_total = axis_size(mesh, "pod", "data")

    # pin activations batch-sharded (GSPMD otherwise propagates weight
    # shardings into activations and replicates the batch)
    from ..models import layers as _L
    if shape.global_batch % dp_total == 0:
        _L.set_activation_sharding(rules.dp)
    else:
        _L.set_activation_sharding(None)
    _L.set_norm_bf16(cfg.norm_bf16)

    pshapes = param_shapes(cfg, tp_pad=tp)
    pspecs = rules.param_specs(pshapes)
    p_sds = _sds_with_sharding(pshapes, rules.named(pspecs))

    bshapes = input_specs(cfg, shape)
    if shape.kind == "decode":
        cache_shapes = bshapes["cache"]
        cspecs = rules.cache_specs(cache_shapes)
        tok_spec = rules.batch_specs({"tokens": bshapes["tokens"]})
        b_sds = {
            "tokens": jax.ShapeDtypeStruct(
                bshapes["tokens"].shape, bshapes["tokens"].dtype,
                sharding=rules.named(tok_spec)["tokens"]),
            "cache": _sds_with_sharding(cache_shapes, rules.named(cspecs)),
            "pos": jax.ShapeDtypeStruct((), np.int32),
        }
        step = make_decode_step(cfg)
        args = (p_sds, b_sds["cache"], b_sds["tokens"], b_sds["pos"])
    elif shape.kind == "prefill":
        bspecs = rules.batch_specs(bshapes)
        b_sds = _sds_with_sharding(bshapes, rules.named(bspecs))
        step = make_prefill_step(cfg)
        args = (p_sds, b_sds)
    else:
        bspecs = rules.batch_specs(bshapes)
        b_sds = _sds_with_sharding(bshapes, rules.named(bspecs))
        oshapes = opt_state_shapes(cfg.optimizer, pshapes)
        ospecs = rules.opt_specs(oshapes, pspecs)
        o_sds = _sds_with_sharding(oshapes, rules.named(ospecs))
        step = make_train_step(cfg, n_groups=dp_total)
        args = (p_sds, o_sds, b_sds)
    return cfg, shape, step, args


def attention_kernel_ideal_bytes(cfg, shape, mesh) -> dict | None:
    """TPU-faithful accounting for attn_impl=flash_pallas (hillclimb H3).

    Interpret-mode Pallas lowers grid steps to HLO loops, so the analyzer
    would charge the kernel's VMEM-resident intermediates as HBM traffic.
    Instead the model is lowered with the math-identical jnp custom-VJP
    flash whose ops are tagged with jax.named_scope('flashattn_*'); the
    analyzer buckets those bytes, and we replace the bucket with the Pallas
    kernel's custom-call boundary traffic (operands + results) — its HBM
    footprint on TPU by construction (see kernels/flash_attention.py).
    FLOPs are unchanged (same dots).  Returns the per-device ideal stream
    bytes to ADD; the measured bucket is subtracted by the caller.
    """
    if shape.kind not in ("train", "prefill"):
        return None
    from ..models import text_len
    import jax.numpy as jnp  # noqa: F401
    tp = mesh.shape.get("model", 1)
    dp = axis_size(mesh, "pod", "data")
    B_loc = max(1, shape.global_batch // dp)
    S = text_len(cfg, shape.seq_len) + (cfg.n_prefix_tokens
                                        if cfg.family == "vlm" else 0)
    Hq = cfg.padded_heads(tp) // tp if cfg.padded_heads(tp) % tp == 0 else \
        cfg.padded_heads(tp)
    kv = cfg.n_kv_heads // tp if (cfg.n_kv_heads and
                                  cfg.n_kv_heads % tp == 0) \
        else cfg.n_kv_heads
    Hq = max(Hq, kv)
    D = -(-cfg.head_dim // 128) * 128       # kernel pads head_dim
    bdt = 2 if cfg.param_dtype == "bfloat16" else 4
    q_b = B_loc * S * Hq * D * bdt
    kv_b = B_loc * S * kv * D * bdt
    lse_b = B_loc * S * Hq * 4
    ideal_fwd = 2 * q_b + 2 * kv_b + lse_b          # read q,k,v; write o,lse
    ideal_bwd = (3 * q_b + 2 * kv_b + 2 * lse_b     # read q,do,o,k,v,lse,dlt
                 + q_b + 2 * kv_b)                  # write dq,dk,dv
    if cfg.family == "encdec":
        n_attn = cfg.enc_layers + 2 * cfg.dec_layers
    elif cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
    elif cfg.family == "ssm":
        return {"add_bytes": 0.0}
    else:
        n_attn = cfg.n_layers
    fwd_passes, bwd_passes = {"train": (2, 1), "prefill": (1, 0)}[shape.kind]
    return {"add_bytes": n_attn * (fwd_passes * ideal_fwd
                                   + bwd_passes * ideal_bwd),
            "ideal_fwd_bytes": ideal_fwd, "ideal_bwd_bytes": ideal_bwd,
            "attn_layers": n_attn}


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             fsdp=True, tag="baseline", overrides=None, extra=None,
             verbose=True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(overrides or {})
    pallas_attn = overrides.get("attn_impl") == "flash_pallas"
    if pallas_attn:
        overrides["attn_impl"] = "flash_cvjp"  # identical math for lowering
    cfg, shape, step, args = build_cell(arch, shape_name, mesh, fsdp=fsdp,
                                        overrides=overrides, extra=extra)
    n_dev = mesh.size
    t0 = time.time()
    with mesh:
        lowered = jax.jit(step).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    st = hlo_analyze(hlo, bucket_re="flashattn" if pallas_attn else None)

    flops_dev = float(st["flops"])
    bytes_dev = float(st["hbm_bytes"])
    coll_dev = float(st["collective_bytes"])
    correction = None
    if pallas_attn:
        correction = attention_kernel_ideal_bytes(cfg, shape, mesh)
        if correction is not None:
            correction["subtract_bytes"] = st["bucket_bytes"]
            bytes_dev = max(0.0, bytes_dev - st["bucket_bytes"]
                            + correction["add_bytes"])
    mf = model_flops(cfg, shape)

    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev, "tag": tag,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_dev,
            "collective_by_type": st["collective_by_type"],
            "collective_counts": st["collective_counts"],
            "xla_cost_analysis_flops_unscaled": float(
                cost.get("flops", 0.0)),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem,
                                            "generated_code_size_in_bytes",
                                            None),
        },
        "model_flops_global": mf,
        "pallas_attn_correction": correction,
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / LINK_BW,
        },
    }
    terms = result["roofline"]
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    result["roofline"]["dominant"] = dom
    hlo_flops_global = flops_dev * n_dev
    result["roofline"]["model_flops_ratio"] = (
        mf / hlo_flops_global if hlo_flops_global else 0.0)
    if verbose:
        print(json.dumps(result["roofline"], indent=2))
        print(f"[{arch} x {shape_name} x {result['mesh']}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"dominant={dom}")
        print("memory:", result["memory"])
    return result


def save_result(res: dict) -> pathlib.Path:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = f"{res['arch']}__{res['shape']}__{res['mesh']}__{res['tag']}.json"
    path = ARTIFACTS / name
    path.write_text(json.dumps(res, indent=2))
    return path


def all_cells():
    out = []
    for arch, cfg in ARCHS.items():
        for sname, shape in SHAPES.items():
            ok, why = shape_applicable(cfg, shape)
            if ok:
                out.append((arch, sname))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="ModelConfig override, e.g. attn_impl=flash_cvjp")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.set)

    if args.list:
        for a, s in all_cells():
            print(f"{a:28s} {s}")
        return

    cells = (all_cells() if args.all
             else [(args.arch, args.shape)])
    for arch, shape in cells:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        out = (ARTIFACTS /
               f"{arch}__{shape}__{mesh_name}__{args.tag}.json")
        if args.skip_existing and out.exists():
            print(f"skip {arch} x {shape} ({out.name} exists)")
            continue
        try:
            res = run_cell(arch, shape, args.multi_pod,
                           fsdp=not args.no_fsdp, tag=args.tag,
                           overrides=overrides)
            p = save_result(res)
            print("saved", p)
        except Exception as e:  # noqa: BLE001 — sweep must continue
            print(f"FAILED {arch} x {shape}: {type(e).__name__}: {e}")
            if not args.all:
                raise


if __name__ == "__main__":
    main()

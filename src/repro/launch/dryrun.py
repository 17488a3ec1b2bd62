import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes — 16x16 single-pod and 2x16x16 two-pod — and extract
memory/cost/collective numbers for EXPERIMENTS.md §Dry-run and §Roofline.

The two lines above MUST precede any other import (jax locks the device
count at first init). Do not set that flag anywhere else in the repo.

Per pair this runs up to three compiles:
  1. full depth, scanned               -> lowering proof + memory_analysis
  2. depth-1 and depth-2, fully        -> collective bytes (and HLO flop
     unrolled ("count compiles")          cross-check), linearly extrapolated
                                          in depth (analysis.extrapolate_depth)
FLOPs/HBM bytes for the roofline come from roofline/analytic.py (XLA's cost
analysis counts scan bodies once — see that module's docstring).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all --proof-only   # skip count compiles
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ALIASES, ARCH_IDS, get_config
from repro.core.masked_adam import MaskedAdamState
from repro.launch import input_specs as ispec
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import rules_for
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models.registry import build
from repro.roofline import analysis
from repro.roofline.analytic import ShapeSpec, analytic_cost

# the production meshes are TPU v5e pods (`launch.mesh.CHIP_PEAKS` key)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _abstract_opt_state(params, m_dtype=jnp.float32):
    # paper-faithful baseline: fp32 Adam moments; hillclimb C trades the
    # first moment to bf16 ("m_bf16" opt).
    return MaskedAdamState(
        m=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, m_dtype), params),
        v=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
        count=jax.ShapeDtypeStruct((), jnp.int32),
    )


def _mask_like(params):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bool_), params)


def resolve_cfg(arch: str, shape_name: str, mesh=None):
    shp = ispec.INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    variant = "native"
    if shp["global_batch"] > 1 and mesh is not None:
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        cfg = cfg.replace(act_sharding=batch_axes)
    if shp["kind"] == "decode_long":
        # long_500k policy (DESIGN.md §6): sub-quadratic required; dense
        # full-attention archs run the sliding-window variant (window=8192).
        if not any(k in ("mamba", "rwkv") for k in cfg.pattern) and not cfg.window_size:
            cfg = cfg.replace(attn_window_override=8192)
            variant = "swa_500k"
    return cfg, variant


def _compile_step(cfg, mesh, shape_name: str, opts: frozenset = frozenset()):
    """Lower + compile one step function for cfg on mesh. Returns compiled.
    opts: §Perf levers — "grad_shard" | "m_bf16" (window_slice lives on cfg)."""
    shp = ispec.INPUT_SHAPES[shape_name]
    kind = shp["kind"]
    model = build(cfg)
    rules = rules_for(cfg, mesh, shape_kind=kind,
                      attn_dp="attn_dp" in opts and kind in ("train", "prefill"),
                      moe_shard="moe_shard" in opts and kind in ("train", "prefill"),
                      decode_ep="decode_ep" in opts)
    if ("moe_shard" in opts and cfg.num_experts and rules.get("experts") is None
            and kind in ("train", "prefill")):
        # Only when experts can't shard over "model" (e.g. mixtral's E=8 on a
        # 16-way axis): pin the capacity buffer over the data axes so GSPMD
        # stops emitting capacity-sized partial-sum all-reduces. When experts
        # DO shard (moonshot/llama4), XLA's inferred layout is already better
        # — measured in EXPERIMENTS.md §Perf B.3/B.4.
        cfg = cfg.replace(
            moe_cap_axes=tuple(a for a in ("pod", "data") if a in mesh.axis_names),
        )
        model = build(cfg)
    pspecs = model.pspecs(rules)
    params = model.abstract()

    if kind == "train":
        step = make_train_step(model, grad_pspecs=pspecs if "grad_shard" in opts else None)
        opt = _abstract_opt_state(params,
                                  m_dtype=jnp.bfloat16 if "m_bf16" in opts else jnp.float32)
        opt_specs = MaskedAdamState(m=pspecs, v=pspecs, count=P())
        batch = ispec.train_inputs(cfg, shp["global_batch"], shp["seq_len"])
        bspecs = ispec.train_input_pspecs(cfg, rules)
        jitted = jax.jit(
            step,
            in_shardings=(pspecs, opt_specs, pspecs, bspecs),
            out_shardings=(pspecs, opt_specs, pspecs, P()),
        )
        lowered = jitted.lower(params, opt, _mask_like(params), batch)
    elif kind == "prefill":
        step = make_prefill_step(model, cache_len=shp["seq_len"])
        batch = ispec.train_inputs(cfg, shp["global_batch"], shp["seq_len"])
        batch.pop("labels")
        bspecs = ispec.train_input_pspecs(cfg, rules)
        bspecs.pop("labels")
        cache_specs = model.cache_pspecs(
            shp["global_batch"], shp["seq_len"], rules, mem_len=cfg.num_xattn_tokens
        )
        logit_spec = P(rules.get("batch"), None, rules.get("vocab"))
        jitted = jax.jit(step, in_shardings=(pspecs, bspecs),
                         out_shardings=(logit_spec, cache_specs))
        lowered = jitted.lower(params, batch)
    else:  # decode / decode_long
        step = make_serve_step(model)
        caches = model.abstract_cache(
            shp["global_batch"], shp["seq_len"], mem_len=cfg.num_xattn_tokens
        )
        cache_specs = model.cache_pspecs(
            shp["global_batch"], shp["seq_len"], rules, mem_len=cfg.num_xattn_tokens
        )
        batch = ispec.decode_inputs(cfg, shp["global_batch"])
        bspecs = ispec.decode_input_pspecs(cfg, rules)
        jitted = jax.jit(step, in_shardings=(pspecs, cache_specs, bspecs),
                         out_shardings=(P(rules.get("batch"), None), cache_specs))
        lowered = jitted.lower(params, caches, batch)
    return lowered.compile()


def _count_cfg(cfg, depth: int, seq_len: int):
    """Depth-reduced, fully-unrolled variant for cost counting."""
    G = cfg.num_groups
    kw = dict(
        num_layers=len(cfg.pattern) * depth,
        scan_unroll=True,
        attn_q_chunk=seq_len,
        attn_kv_chunk=seq_len,
    )
    if cfg.encoder_layers:
        kw["encoder_layers"] = max(1, cfg.encoder_layers // G) * depth
    return cfg.replace(**kw)


def lower_pair(arch: str, shape_name: str, mesh, *, verbose: bool = True,
               proof_only: bool = False, cfg_override=None,
               opts: frozenset = frozenset()) -> dict:
    shp = ispec.INPUT_SHAPES[shape_name]
    cfg, variant = resolve_cfg(arch, shape_name, mesh)
    if cfg_override is not None:
        cfg = cfg_override
    if "window_slice" in opts:
        cfg = cfg.replace(decode_window_slicing=True)
    jax.set_mesh(mesh)
    chips = int(jnp.prod(jnp.array(mesh.devices.shape)))

    t0 = time.time()
    compiled = _compile_step(cfg, mesh, shape_name, opts=opts)
    full = analysis.hlo_facts(compiled)
    t_full = time.time() - t0

    spec = ShapeSpec(kind=shp["kind"], seq_len=shp["seq_len"],
                     global_batch=shp["global_batch"])
    ana = analytic_cost(cfg, spec)

    facts = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "opts": sorted(opts),
        "mesh": "x".join(map(str, mesh.devices.shape)), "chips": chips,
        "compile_s": round(t_full, 1),
        "flops": ana["flops"], "bytes": ana["bytes"],
        "model_flops": ana["model_flops"],
        "hlo_flops_scan_once": full["hlo_flops"],
        "device_temp_bytes": full["device_temp_bytes"],
        "device_arg_bytes": full["device_arg_bytes"],
        # scan-aware: while-body collectives x trip count (analysis.py)
        "collective_bytes": float(full["collective"]["sum"]),
        "collective_counts": full["collective"]["counts"],
        "collective_totals": full["collective"]["totals"],
    }

    facts.update(analysis.roofline_terms(
        facts["flops"], facts["bytes"], facts["collective_bytes"], chips,
        device_kind=TARGET_DEVICE_KIND))
    facts["useful_flops_ratio"] = (
        facts["model_flops"] / facts["flops"] if facts["flops"] else 0.0
    )

    if verbose:
        print(f"[{arch} | {shape_name} | mesh {facts['mesh']} | {variant}] "
              f"compile {facts['compile_s']}s bottleneck={facts['bottleneck']}")
        print(f"  flops={facts['flops']:.3e} bytes={facts['bytes']:.3e} "
              f"coll={facts['collective_bytes']:.3e} "
              f"t=(c {facts['t_compute_s']*1e3:.2f} | m {facts['t_memory_s']*1e3:.2f} "
              f"| n {facts['t_collective_s']*1e3:.2f}) ms "
              f"useful={facts['useful_flops_ratio']:.2f}")
        print(f"  per-device: args {facts['device_arg_bytes']/2**30:.2f} GiB, "
              f"temps {facts['device_temp_bytes']/2**30:.2f} GiB")
    return facts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(ispec.INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--proof-only", action="store_true",
                    help="skip the count compiles (lowering proof + memory only)")
    ap.add_argument("--json", default=None, help="append results (json-lines)")
    ap.add_argument("--opt", action="append", default=[],
                    choices=["grad_shard", "m_bf16", "window_slice", "attn_dp",
                             "moe_shard", "decode_ep"],
                    help="§Perf levers (repeatable)")
    ap.add_argument("--optimized", action="store_true",
                    help="enable the validated §Perf levers")
    args = ap.parse_args(argv)
    opts = frozenset(args.opt) if not args.optimized else frozenset(
        ["m_bf16", "window_slice", "moe_shard", "decode_ep"])

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    if args.all:
        pairs = [(a, s) for a in ARCH_IDS for s in ispec.INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    ok, fail = 0, []
    for arch, shape in pairs:
        try:
            facts = lower_pair(arch, shape, mesh, proof_only=args.proof_only, opts=opts)
            ok += 1
            if args.json:
                with open(args.json, "a") as f:
                    f.write(json.dumps(facts) + "\n")
        except Exception as e:  # noqa: BLE001
            fail.append((arch, shape, repr(e)[:300]))
            print(f"[{arch} | {shape}] FAILED: {e}", file=sys.stderr)
    print(f"\ndry-run: {ok} ok, {len(fail)} failed")
    for f in fail:
        print("  FAIL", *f)
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

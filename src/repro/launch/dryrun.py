import os
os.environ["JAX_PLATFORMS"] = "cpu"     # never takes an attached chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

Lowers + compiles the production step for one (architecture x input shape
x mesh) combination with ShapeDtypeStruct inputs — no device allocation —
and reports memory analysis, cost analysis (FLOPs / bytes) and the
collective traffic parsed from the partitioned HLO.  This is the proof
that the distribution config is coherent, and the data source for the
roofline analysis (EXPERIMENTS.md §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k [--multi-pod] [--json out.json]

The environment lines above MUST stay first: jax fixes its platform and
device count on first initialization.  The run is pinned to the CPU, so a
sweep's children never take a chip that another process holds.
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch, supports_shape
from repro.configs.base import ArchConfig
from repro.launch import hlo_analysis
from repro.launch.inputs import decode_specs, input_specs, n_micro_for
from repro.launch.mesh import (DCN_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models.model import build_model
from repro.optim import AdamW, constant
from repro.serve.decode import make_serve_step
from repro.sharding import (batch_specs, cache_specs, data_axes_of,
                            param_specs, to_named, train_state_specs)
from repro.train.state import abstract_train_state
from repro.train.step import make_train_step


def apply_variant(cfg: ArchConfig, variant: str):
    """Perf-iteration variants (EXPERIMENTS.md §Perf).

    baseline          paper-faithful lowering (jnp blocked attention)
    flash             flash-custom-VJP attention (no O(S^2) scan saves)
    fusednorm         analytic custom-VJP RMSNorm (one fused backward)
    seqpar            sequence-parallel TP: residual stream sequence dim
                      sharded over the model axis between blocks
    moe3d             3-D (E, C, d) MoE dispatch buffer (expert dim
                      shardable; kills the replicated (T*K, d) gather)
    moesm             shard_map expert parallelism: shard-local dispatch
                      + one (T_local, d) psum over the model axis
    fsdp              ZeRO-3: parameters also sharded over the data axes
    cachemodel        decode KV caches additionally sharded over the
                      model axis on the capacity dim (residency fix)
    ep48              granite-moe: pad 40 -> 48 experts so the expert dim
                      divides the model axis (expert parallelism instead
                      of intra-expert TP); capacity scaled to keep FLOPs
    Tokens compose with '+': e.g. 'flash+ep48'.
    """
    import dataclasses
    kernel = "jnp"
    for tok in variant.split("+"):
        if tok == "flash":
            kernel = "flash"
        elif tok == "fusednorm":
            from repro.models import layers
            layers.RMSNORM_FUSED = True
        elif tok == "seqpar":
            pass                      # applied in lower_pair (needs mesh)
        elif tok == "moe3d":
            from repro.models import moe
            moe.DISPATCH_3D = True
        elif tok == "moesm":
            pass                      # applied in lower_pair (needs mesh)
        elif tok == "fsdp":
            pass                      # applied in lower_pair (train only)
        elif tok == "cachemodel":
            pass                      # applied in lower_pair (decode only)
        elif tok == "ep48" and cfg.moe is not None:
            m = cfg.moe
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                m, n_experts=48,
                capacity_factor=m.capacity_factor * m.n_experts / 48))
        elif tok not in ("baseline", ""):
            raise ValueError(f"unknown variant token {tok!r}")
    return cfg, kernel


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               variant: str = "baseline"):
    """Returns (lowered, meta) for one (arch, shape, mesh)."""
    cfg = get_arch(arch)
    cfg, kernel = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes, dp = data_axes_of(mesh)
    if "seqpar" in variant.split("+"):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import model as model_mod
        da = data_axes if len(data_axes) > 1 else data_axes[0]
        model_mod.SEQ_SHARDING = NamedSharding(mesh, P(da, "model", None))
    if "moesm" in variant.split("+"):
        from repro.models import moe as moe_mod
        moe_mod.SHARD_MAP = (mesh, data_axes)
    model_size = mesh.shape["model"]
    model = build_model(cfg)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": shape.kind, "dp": dp, "tp": model_size,
            "variant": variant}

    if shape.kind == "train":
        opt = AdamW(lr=constant(3e-4))
        state_sds = abstract_train_state(model, opt)
        state_specs = train_state_specs(
            state_sds, mesh, fsdp="fsdp" in variant.split("+"))
        n = n_micro_for(shape, dp)
        batch_sds = input_specs(cfg, shape, dp)
        bspecs = batch_specs(batch_sds, data_axes, dp, stacked=True)
        step = make_train_step(model, opt, n, kernel=kernel, remat=True)
        meta["n_micro"] = n
        jitted = jax.jit(step, in_shardings=(
            to_named(mesh, state_specs), to_named(mesh, bspecs)))
        lowered = jitted.lower(state_sds, batch_sds)
    elif shape.kind == "prefill":
        params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = param_specs(params_sds, model_size)
        batch_sds = input_specs(cfg, shape, dp)
        bspecs = batch_specs(batch_sds, data_axes, dp, stacked=False)

        def prefill_step(params, batch):
            logits, _ = model.forward(params, batch, kernel=kernel,
                                      remat=True, last_logits_only=True)
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        jitted = jax.jit(prefill_step, in_shardings=(
            to_named(mesh, pspecs), to_named(mesh, bspecs)))
        lowered = jitted.lower(params_sds, batch_sds)
    else:  # decode
        params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = param_specs(params_sds, model_size)
        caches_sds, tok_sds, pos_sds = decode_specs(model, cfg, shape)
        shard_seq = shape.name == "long_500k"
        cspecs = cache_specs(caches_sds, data_axes, dp, model_size,
                             shard_seq=shard_seq,
                             kv_model="cachemodel" in variant.split("+"))
        da = data_axes if len(data_axes) > 1 else data_axes[0]
        tok_spec = (jax.sharding.PartitionSpec(da)
                    if shape.global_batch % dp == 0 and shape.global_batch > 1
                    else jax.sharding.PartitionSpec())
        serve = make_serve_step(model)
        jitted = jax.jit(serve, in_shardings=(
            to_named(mesh, pspecs), to_named(mesh, cspecs),
            jax.sharding.NamedSharding(mesh, tok_spec),
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())))
        lowered = jitted.lower(params_sds, caches_sds, tok_sds, pos_sds)
    return lowered, meta


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                                  # noqa: BLE001
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "serialized_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   n_chips: int, multi_pod: bool) -> dict:
    """Three roofline terms in seconds (per spec: totals over the chips'
    aggregate capability; cost_analysis numbers are per-device module,
    i.e. already divided by the chip count)."""
    link_bw = DCN_BW if multi_pod else ICI_BW
    return {
        "compute_s": flops / (PEAK_FLOPS_BF16),
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_bytes / link_bw,
    }


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             variant: str = "baseline", verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip", "reason": reason}
    t0 = time.time()
    lowered, meta = lower_pair(arch, shape_name, multi_pod=multi_pod,
                               variant=variant)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # raw XLA cost analysis (visits while bodies once — kept for reference)
    cost = compiled.cost_analysis() or {}
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    # loop-aware static analysis (the roofline source)
    text = compiled.as_text()
    acc = hlo_analysis.analyze(text)
    flops, hbm, coll = acc.flops, acc.bytes, acc.coll_bytes
    n_chips = 512 if multi_pod else 256
    mem = _mem_dict(compiled)

    result = dict(meta)
    result.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops": flops,
        "hlo_bytes": hbm,
        "collective_bytes": coll,
        "collectives": {k: v for k, v in acc.coll.items() if v["count"]},
        "bytes_by_op": dict(sorted(acc.bytes_by_op.items(),
                                   key=lambda kv: -kv[1])),
        "xla_cost_analysis": {"flops": raw_flops,
                              "bytes_accessed": raw_bytes},
        "memory": mem,
        "roofline": roofline_terms(flops, hbm, coll, n_chips, multi_pod),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })
    # MODEL_FLOPS = 6*N_active*D for one step's tokens
    n = shape.global_batch * shape.seq_len if shape.kind != "decode" \
        else shape.global_batch
    mf = 6.0 * cfg.active_param_count() * n
    if shape.kind != "train":
        mf /= 3.0                  # inference fwd-only: 2*N*D
    result["model_flops"] = mf
    total_hlo = flops * n_chips
    result["model_flops_ratio"] = (mf / total_hlo) if total_hlo else 0.0
    if verbose:
        print(json.dumps(result, indent=2), flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--json", default=None, help="append result to file")
    args = ap.parse_args()

    res = run_pair(args.arch, args.shape, multi_pod=args.multi_pod,
                   variant=args.variant)
    if args.json:
        with open(args.json, "a") as f:
            f.write(json.dumps(res) + "\n")
    sys.exit(0 if res.get("status") in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()

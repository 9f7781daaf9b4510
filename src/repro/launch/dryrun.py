import os

# A CPU tool: it and the children it starts never take an accelerator (one
# process owns a chip, and this one only compiles for described meshes).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production meshes and record memory / cost /
collective analysis.

MUST be executed as its own process (``python -m repro.launch.dryrun``) —
the XLA_FLAGS assignment above precedes every jax import, including the
``from repro...`` ones, because jax locks the device count at first init.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out results/dryrun]   # subprocess per combo

Unlike the training front doors (``repro.api.run`` / ``repro.launch.train``,
which consume a declarative ``repro.api.ExperimentSpec``), the dry-run
deliberately sits below the spec layer: it sweeps raw (arch, shape, mesh)
combos with abstract inputs and never builds a dataset or sampler.
"""
import argparse
import functools
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import active_params, model_flops
from repro.configs import INPUT_SHAPES, get_config, input_specs, list_archs, step_kind
from repro.fed.round import RoundSpec, build_round_step
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.sharding import (
    activation_rules,
    cache_shardings,
    param_shardings,
    param_specs,
)
from repro.models import sharding as msharding
from repro.models import transformer

COHORT_PARALLEL = 16  # clients per round, client_parallel (= data-axis size)
COHORT_SEQUENTIAL = 4  # scan length, cohort_sequential
LOCAL_STEPS = 2


def _long_cfg(arch: str):
    """Arch config used for the long_500k shape (sliding-window variant for
    the dense long-context entry)."""
    if arch == "llama3.2-1b":
        from repro.configs.llama3_2_1b import SW_CONFIG

        return SW_CONFIG
    return get_config(arch)


def _cfg_for(arch: str, shape_name: str):
    return _long_cfg(arch) if shape_name == "long_500k" else get_config(arch)


def _abstract_params(cfg):
    return jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))


def _train_setup(cfg, shape, mesh):
    """Lower the federated round step (the paper's technique IS the train step)."""
    cohort = COHORT_PARALLEL if cfg.round_mode == "client_parallel" else COHORT_SEQUENTIAL
    if cfg.round_mode == "client_parallel" and "pod" in mesh.axis_names:
        cohort *= mesh.shape["pod"]
    b_local = shape.global_batch // (cohort * LOCAL_STEPS)
    assert b_local >= 1, (cfg.name, shape.name, cohort)
    spec = RoundSpec(cohort=cohort, local_steps=LOCAL_STEPS, local_lr=0.02)

    params = _abstract_params(cfg)
    fsdp = cfg.round_mode == "cohort_sequential"
    p_shard = param_shardings(params, mesh, fsdp=fsdp)

    if os.environ.get("REPRO_NO_ACC_CONSTRAINT"):
        constrain = None  # reproduces the pre-fix baseline (qwen3 iter 1)
    else:
        constrain = lambda tree: jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, p_shard
        )
    round_step = build_round_step(cfg, spec, constrain=constrain)
    b_axes = batch_axes(mesh)
    tok = jax.ShapeDtypeStruct((cohort, LOCAL_STEPS, b_local, shape.seq_len), jnp.int32)
    w = jax.ShapeDtypeStruct((cohort,), jnp.float32)
    if cfg.round_mode == "client_parallel":
        data_in = NamedSharding(mesh, P(b_axes))  # clients over batch axes
    else:
        data_in = NamedSharding(mesh, P(None, None, b_axes))  # batch-per-client
    args = [params, tok, tok, w]
    in_sh = [p_shard, data_in, data_in, NamedSharding(mesh, P())]
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        aux = jax.ShapeDtypeStruct(
            (cohort, LOCAL_STEPS, b_local, cfg.frontend_seq, fd), jnp.float32
        )
        if cfg.round_mode == "client_parallel":
            aux_sh = NamedSharding(mesh, P(b_axes))
        else:
            aux_sh = NamedSharding(mesh, P(None, None, b_axes))
        args.append(aux)
        in_sh.append(aux_sh)
    out_sh = (p_shard, NamedSharding(mesh, P()), NamedSharding(mesh, P()))
    fn = jax.jit(
        round_step, in_shardings=tuple(in_sh), out_shardings=out_sh,
        donate_argnums=(0,),
    )
    tokens_processed = shape.global_batch * shape.seq_len
    return fn, args, tokens_processed, "train"


def _prefill_setup(cfg, shape, mesh):
    params = _abstract_params(cfg)
    fsdp = cfg.round_mode == "cohort_sequential"
    p_shard = param_shardings(params, mesh, fsdp=fsdp)
    b_axes = batch_axes(mesh)
    specs = input_specs(cfg, shape)
    args = [params, specs["tokens"]]
    in_sh = [p_shard, NamedSharding(mesh, P(b_axes))]
    kwargs = {}
    if "aux_embeds" in specs:
        args.append(specs["aux_embeds"])
        in_sh.append(NamedSharding(mesh, P(b_axes)))

    def fn(params, tokens, aux=None):
        return transformer.prefill(params, cfg, tokens, aux)

    jfn = jax.jit(fn, in_shardings=tuple(in_sh))
    tokens_processed = shape.global_batch * shape.seq_len
    return jfn, args, tokens_processed, "prefill"


def _decode_setup(cfg, shape, mesh):
    params = _abstract_params(cfg)
    fsdp = cfg.round_mode == "cohort_sequential"
    p_shard = param_shardings(params, mesh, fsdp=fsdp)
    b_axes = batch_axes(mesh)
    specs = input_specs(cfg, shape)
    caches = specs["caches"]
    c_shard = cache_shardings(caches, mesh, shape.seq_len, shape.global_batch)
    b_size = 1
    for a in b_axes:
        b_size *= mesh.shape[a]
    tok_sh = (
        NamedSharding(mesh, P(b_axes))
        if shape.global_batch % b_size == 0 and shape.global_batch > 1
        else NamedSharding(mesh, P())
    )

    def fn(params, token, caches, index):
        return transformer.decode_step(params, cfg, token, caches, index)

    jfn = jax.jit(
        fn,
        in_shardings=(p_shard, tok_sh, c_shard, NamedSharding(mesh, P())),
    )
    args = [params, specs["token"], caches, specs["index"]]
    tokens_processed = shape.global_batch  # one new token per sequence
    return jfn, args, tokens_processed, "decode"


def run_one(arch: str, shape_name: str, multi_pod: bool, opts: tuple = ()) -> dict:
    """opts: perf-variant switches recorded in EXPERIMENTS.md section Perf:
      seq_parallel   — shard the residual-stream sequence dim over `model`
                       (universal balance for non-divisible head counts)
      remat_none     — disable layer-group gradient checkpointing
      mlstm_chunked  — chunkwise-parallel mLSTM cell (see models/xlstm.py)
    """
    import dataclasses as _dc

    shape = INPUT_SHAPES[shape_name]
    cfg = _cfg_for(arch, shape_name)
    if "remat_none" in opts:
        cfg = _dc.replace(cfg, remat="none")
    if "attn_chunked" in opts:
        cfg = _dc.replace(cfg, attn_impl="chunked")
    if "moe_a2a" in opts:
        cfg = _dc.replace(cfg, moe_impl="a2a")
    if "mlstm_chunked" in opts:
        cfg = _dc.replace(cfg, mlstm_impl="chunked")
    for o in opts:
        if o.startswith("mlstm_chunk_"):
            cfg = _dc.replace(cfg, mlstm_impl="chunked", mlstm_chunk=int(o.rsplit("_", 1)[1]))
        if o.startswith("slstm_seg_"):
            cfg = _dc.replace(cfg, slstm_segment=int(o.rsplit("_", 1)[1]))
    kind = step_kind(cfg, shape)
    if kind is None:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod, "status": "skip",
                "reason": "full-attention arch skips long_500k (DESIGN.md section 4)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    setup = {"train": _train_setup, "prefill": _prefill_setup, "decode": _decode_setup}[kind]
    long_ctx = shape_name == "long_500k"
    cp = kind == "train" and cfg.round_mode == "client_parallel"
    rules = activation_rules(mesh, long_context=long_ctx, client_parallel=cp)
    if "seq_parallel" in opts:
        rules["seq"] = ("model",)
    with msharding.use_rules(mesh, rules):
        fn, args, tokens_processed, kind = setup(cfg, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    walk = analyze_hlo(hlo)  # trip-count-aware (cost_analysis counts scan bodies once)

    n_chips = mesh.devices.size
    params_abs = _abstract_params(cfg)
    n_active = active_params(cfg, params_abs)
    mf = model_flops(n_active, tokens_processed, kind)

    result = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "opts": list(opts),
        "status": "ok",
        "kind": kind,
        "n_chips": n_chips,
        "round_mode": cfg.round_mode,
        "flops": walk["flops"],
        "bytes_accessed": walk["bytes"],
        "collective_bytes": walk["collective_bytes"],
        "collectives": walk["collectives"],
        "raw_cost_analysis": {
            "flops_scan_body_once": float(cost.get("flops", 0.0)),
            "bytes_scan_body_once": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_size_bytes": int(mem.argument_size_in_bytes),
            "output_size_bytes": int(mem.output_size_in_bytes),
            "temp_size_bytes": int(mem.temp_size_in_bytes),
            "generated_code_size_bytes": int(mem.generated_code_size_in_bytes),
        },
        "active_params": float(n_active),
        "tokens_processed": float(tokens_processed),
        "model_flops": float(mf),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--opt", default="", help="comma-separated perf variants")
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.out, exist_ok=True)
        combos = []
        for arch in list_archs():
            for shape_name in INPUT_SHAPES:
                for mp in (False, True):
                    combos.append((arch, shape_name, mp))
        for arch, shape_name, mp in combos:
            tag = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print("cached", tag)
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape_name,
            ] + (["--multi-pod"] if mp else [])
            print(">>>", tag, flush=True)
            try:
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=args.timeout,
                    env={**os.environ, "PYTHONPATH": "src"},
                )
                if proc.returncode == 0:
                    # last line of stdout is the JSON result
                    result = json.loads(proc.stdout.strip().splitlines()[-1])
                else:
                    result = {
                        "arch": arch, "shape": shape_name, "multi_pod": mp,
                        "status": "error", "stderr": proc.stderr[-4000:],
                    }
            except subprocess.TimeoutExpired:
                result = {"arch": arch, "shape": shape_name, "multi_pod": mp,
                          "status": "timeout"}
            with open(path, "w") as f:
                json.dump(result, f, indent=1)
            print(
                "   ", result["status"],
                f"compile={result.get('compile_s', '-')}s" if result["status"] == "ok" else "",
                flush=True,
            )
        return

    opts = tuple(o for o in args.opt.split(",") if o)
    result = run_one(args.arch, INPUT_SHAPES[args.shape].name, args.multi_pod, opts)
    print(json.dumps(result))


if __name__ == "__main__":
    main()

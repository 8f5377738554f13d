import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count at first
# init, and the production meshes need 512 placeholder devices.

"""Multi-pod dry-run launcher.

Per cell (arch x input-shape x mesh): build ShapeDtypeStruct inputs with
production shardings, ``jax.jit(step).lower(...).compile()``, print
memory_analysis (proves the per-device footprint) + cost_analysis (FLOPs /
bytes for the roofline), parse the partitioned HLO for collective bytes,
and append the JSON record to benchmarks/results/dryrun/.

Worker mode:      python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
Orchestrator:     python -m repro.launch.dryrun --all [--mesh single|multi|both]
(the orchestrator shells out one subprocess per cell so each gets a fresh
XLA runtime and an enforceable timeout).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Optional

from repro.obs.log import configure_logging, get_logger

log = get_logger("launch.dryrun")

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def cell_filename(arch: str, shape: str, mesh: str, tag: str = "") -> str:
    suffix = f"_{tag}" if tag else ""
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh}{suffix}.json")


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             fsdp: bool = True, freeze_prefix: float = 0.0,
             remat: Optional[str] = None, tag: str = "",
             print_analysis: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import cell_is_applicable, get_config, get_shape
    from repro.core.freeze_plan import FreezePlan
    from repro.distributed import sharding as sh
    from repro.launch import specs as S
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    from repro.roofline import analysis as RA

    t0 = time.time()
    cfg = get_config(arch).replace(ssm_chunk=2048, attn_q_block=4096,
                                   attn_k_block=4096)
    if remat:
        cfg = cfg.replace(remat=remat)
    # Perf-iteration hook: REPRO_OVERRIDES="field=value,..." patches the
    # ModelConfig (types coerced from the field's current value).
    for kv in filter(None, os.environ.get("REPRO_OVERRIDES", "").split(",")):
        key, val = kv.split("=")
        cur = getattr(cfg, key)
        typ = type(cur)
        coerced = (val.lower() in ("1", "true")) if typ is bool else typ(val)
        cfg = cfg.replace(**{key: coerced})
    shape = get_shape(shape_name)
    skip = cell_is_applicable(cfg, shape)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "fsdp": fsdp, "freeze_prefix": freeze_prefix, "tag": tag,
              "remat": cfg.remat}
    if skip:
        record.update({"status": "skip", "reason": skip})
        return record

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    policy = sh.ShardingPolicy(fsdp=fsdp)
    # bf16 optimizer moments for >=100B-param configs (DESIGN.md §4)
    big = cfg.param_count() > 100e9
    opt_cfg = AdamWConfig(lr=1e-4, state_dtype="bfloat16" if big else None,
                          clip_norm=0.0)

    params_sds, param_spec = S.param_structs(cfg, mesh, policy)

    if shape.kind == "train":
        batch_sds = S.train_batch_specs(cfg, shape, mesh, policy)
        G = T.num_groups(cfg)
        k = int(G * freeze_prefix)
        plan = FreezePlan(groups=tuple(i < k for i in range(G)),
                          embed=k > 0) if k else None

        def train_step(params, opt_state, batch):
            (loss, _), grads = jax.value_and_grad(
                lambda p: T.lm_loss(p, cfg, batch, plan), has_aux=True)(params)
            params, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
            return params, opt_state, loss

        opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
        from repro.optim.optimizer import AdamWState
        opt_spec = AdamWState(step=jax.sharding.PartitionSpec(),
                              m=param_spec, v=param_spec)
        opt_sds = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.NamedSharding(mesh, sp)),
            opt_sds, opt_spec,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        jitted = jax.jit(
            train_step,
            in_shardings=(sh.named(mesh, param_spec),
                          sh.named(mesh, opt_spec),
                          sh.named(mesh, sh.batch_specs(cfg, shape, mesh, policy))),
            donate_argnums=(0, 1))
        with sh.activation_sharding(mesh):
            lowered = jitted.lower(params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        batch_sds = S.prefill_batch_specs(cfg, shape, mesh, policy)

        def prefill_step(params, batch):
            return T.lm_prefill(params, cfg, batch)

        # batch shardings come from the ShapeDtypeStructs themselves
        jitted = jax.jit(prefill_step)
        with sh.activation_sharding(mesh):
            lowered = jitted.lower(params_sds, batch_sds)
    else:  # decode
        cache_sds, cache_spec = S.cache_structs(cfg, shape, mesh, policy)
        tok_sds = S.decode_token_specs(cfg, shape, mesh, policy)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def decode_step(params, cache, tokens, pos):
            return T.lm_decode(params, cfg, tokens, cache, pos)

        jitted = jax.jit(decode_step, donate_argnums=(1,))
        with sh.activation_sharding(mesh):
            lowered = jitted.lower(params_sds, cache_sds, tok_sds, pos_sds)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    if print_analysis:
        log.info("[%s x %s x %s] memory_analysis: %s",
                 arch, shape_name, mesh_name, mem)
        ca = compiled.cost_analysis()
        log.info("[%s x %s x %s] cost_analysis: %s",
                 arch, shape_name, mesh_name,
                 {k: ca[k] for k in ("flops", "bytes accessed") if k in ca})

    rep = RA.analyze(compiled, arch=arch, shape=shape_name,
                     mesh_name=mesh_name, chips=chips,
                     model_flops=RA.model_flops_estimate(cfg, shape))

    if mesh_name == "single":
        # --- depth-probe extrapolation for the roofline terms -----------
        # XLA:CPU cost_analysis counts a while-loop body ONCE regardless of
        # trip count, so the rolled full-depth compile above (which proves
        # compilation + gives the honest memory picture) undercounts FLOPs,
        # bytes and collective ops by ~G. Shallow UNROLLED probes give
        # exact per-group costs; extrapolation reconstructs full depth
        # (layers are depth-homogeneous in all 10 archs).
        g = T.group_size(cfg)
        G = T.num_groups(cfg)
        if not freeze_prefix:
            p1 = _probe_costs(arch, shape_name, cfg.replace(
                num_layers=g, scan_unroll=True), shape, mesh, policy,
                opt_cfg, 0, 0)
            p2 = _probe_costs(arch, shape_name, cfg.replace(
                num_layers=2 * g, scan_unroll=True), shape, mesh, policy,
                opt_cfg, 0, 0)
            per_group = {k: p2[k] - p1[k] for k in p1}
            outer = {k: p1[k] - per_group[k] for k in p1}
            tot = {k: outer[k] + G * per_group[k] for k in p1}
        else:
            # Frozen and active groups cost differently -> 3 probes:
            #   f21 = outer + fr + ac    (2 groups, first frozen)
            #   f41 = outer + fr + 3ac   (4 groups, first frozen)
            #   f42 = outer + 2fr + 2ac  (4 groups, first two frozen)
            # ac = (f41-f21)/2; fr = f42-f41+ac; outer = f21-fr-ac;
            # total = outer + k*fr + (G-k)*ac  with k = int(G*prefix).
            f21 = _probe_costs(arch, shape_name, cfg.replace(
                num_layers=2 * g, scan_unroll=True), shape, mesh, policy,
                opt_cfg, 1, 2)
            f41 = _probe_costs(arch, shape_name, cfg.replace(
                num_layers=4 * g, scan_unroll=True), shape, mesh, policy,
                opt_cfg, 1, 4)
            f42 = _probe_costs(arch, shape_name, cfg.replace(
                num_layers=4 * g, scan_unroll=True), shape, mesh, policy,
                opt_cfg, 2, 4)
            k_full = int(G * freeze_prefix)
            tot, per_group, outer = {}, {}, {}
            for key in f21:
                ac = (f41[key] - f21[key]) / 2.0
                fr = f42[key] - f41[key] + ac
                out_ = f21[key] - fr - ac
                tot[key] = out_ + k_full * fr + (G - k_full) * ac
                per_group[key] = ac
                outer[key] = out_
        rep.flops_per_chip = max(tot["flops"], 0.0)
        rep.bytes_per_chip = max(tot["bytes"], 0.0)
        rep.collective_bytes_per_chip = max(tot["coll"], 0.0)
        rep.finalize()
        record["probe_per_group"] = per_group
        record["probe_outer"] = outer

    record.update({"status": "ok", "lower_s": round(t_lower, 1),
                   "compile_s": round(t_compile, 1), **rep.to_dict()})
    return record


def _probe_costs(arch, shape_name, cfg, shape, mesh, policy, opt_cfg,
                 frozen_groups, total_groups=0):
    """Compile a shallow unrolled variant; return per-chip flops/bytes/
    collective bytes. `frozen_groups` freezes that many leading groups
    (+ the embedding) to probe frozen-group costs."""
    import jax
    import jax.numpy as jnp

    from repro.core.freeze_plan import FreezePlan
    from repro.distributed import sharding as sh
    from repro.launch import specs as S
    from repro.models import transformer as T
    from repro.optim import adamw_init, adamw_update
    from repro.optim.optimizer import AdamWState
    from repro.roofline import analysis as RA

    params_sds, param_spec = S.param_structs(cfg, mesh, policy)
    if shape.kind == "train":
        batch_sds = S.train_batch_specs(cfg, shape, mesh, policy)
        G = T.num_groups(cfg)
        k = frozen_groups
        plan = FreezePlan(groups=tuple(i < k for i in range(G)),
                          embed=k > 0) if k else None

        def step(params, opt_state, batch):
            (loss, _), grads = jax.value_and_grad(
                lambda p: T.lm_loss(p, cfg, batch, plan), has_aux=True)(params)
            params, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
            return params, opt_state, loss

        opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
        opt_spec = AdamWState(step=jax.sharding.PartitionSpec(),
                              m=param_spec, v=param_spec)
        opt_sds = jax.tree.map(
            lambda s_, sp: jax.ShapeDtypeStruct(
                s_.shape, s_.dtype,
                sharding=jax.sharding.NamedSharding(mesh, sp)),
            opt_sds, opt_spec,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        with sh.activation_sharding(mesh):
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, batch_sds).compile()
    elif shape.kind == "prefill":
        batch_sds = S.prefill_batch_specs(cfg, shape, mesh, policy)
        with sh.activation_sharding(mesh):
            compiled = jax.jit(
                lambda p, b: T.lm_prefill(p, cfg, b)).lower(
                    params_sds, batch_sds).compile()
    else:
        cache_sds, _ = S.cache_structs(cfg, shape, mesh, policy)
        tok_sds = S.decode_token_specs(cfg, shape, mesh, policy)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        with sh.activation_sharding(mesh):
            compiled = jax.jit(
                lambda p, c, t, i: T.lm_decode(p, cfg, t, c, i),
                donate_argnums=(1,)).lower(
                    params_sds, cache_sds, tok_sds, pos_sds).compile()
    ca = compiled.cost_analysis()
    stats = RA.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": stats.bytes_per_chip}


def save_record(record: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = cell_filename(record["arch"], record["shape"], record["mesh"],
                         record.get("tag", ""))
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return path


def orchestrate(mesh_modes, archs=None, shapes=None, timeout=2400,
                tag="", extra_args=()):
    from repro.configs import ARCHS, LM_SHAPES

    archs = archs or list(ARCHS)
    shapes = shapes or [s.name for s in LM_SHAPES]
    failures = []
    for mesh_name in mesh_modes:
        for arch in archs:
            for shape in shapes:
                out = cell_filename(arch, shape, mesh_name, tag)
                if os.path.exists(out):
                    log.info("skip existing %s", out)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh_name,
                       "--save"] + list(extra_args)
                if tag:
                    cmd += ["--tag", tag]
                log.info(">> %s", " ".join(cmd))
                try:
                    r = subprocess.run(cmd, timeout=timeout)
                    if r.returncode != 0:
                        failures.append((arch, shape, mesh_name, r.returncode))
                except subprocess.TimeoutExpired:
                    failures.append((arch, shape, mesh_name, "timeout"))
    if failures:
        log.error("FAILURES: %s", failures)
        return 1
    log.info("all cells complete")
    return 0


def main():
    configure_logging(os.environ.get("EDGEOL_LOG") or "INFO")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--freeze-prefix", type=float, default=0.0)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    if args.all:
        modes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        extra = []
        if args.no_fsdp:
            extra.append("--no-fsdp")
        if args.remat:
            extra += ["--remat", args.remat]
        if args.freeze_prefix:
            extra += ["--freeze-prefix", str(args.freeze_prefix)]
        sys.exit(orchestrate(modes, timeout=args.timeout, tag=args.tag,
                             extra_args=extra))

    try:
        record = run_cell(args.arch, args.shape, args.mesh,
                          fsdp=not args.no_fsdp,
                          freeze_prefix=args.freeze_prefix,
                          remat=args.remat, tag=args.tag)
    except Exception as e:
        record = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:], "tag": args.tag}
        if args.save:
            save_record(record)
        # the JSON record is the worker's machine-readable stdout
        # contract; diagnostics go through the logger (stderr)
        sys.stdout.write(json.dumps(
            {k: v for k, v in record.items() if k != "traceback"},
            indent=1) + "\n")
        log.error("cell failed:\n%s", record["traceback"])
        sys.exit(2)
    if args.save:
        path = save_record(record)
        log.info("saved %s", path)
    sys.stdout.write(json.dumps(record, indent=1) + "\n")


if __name__ == "__main__":
    main()

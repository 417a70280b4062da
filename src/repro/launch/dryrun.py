import os

from repro.xla_env import force_host_devices

force_host_devices(os.environ.get("REPRO_HOST_DEVICES", "512"))
# ^ MUST run before jax is imported: jax locks the device count on first
#   init.  Smoke tests / benches never import this module and see 1 device.

# Multi-pod dry-run: lower + compile every (architecture x input shape) cell
# on the production mesh, without allocating a single parameter.
#
# For each cell we record: per-device HLO FLOPs/bytes (cost_analysis),
# memory_analysis, collective traffic parsed from the compiled HLO, and the
# three roofline terms (EXPERIMENTS.md §Dry-run / §Roofline read these JSON
# reports).
#
# Usage:
#   python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
#   python -m repro.launch.dryrun --all [--multi-pod] [--fl-round]

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, SHAPES, TrainConfig, HeliosConfig,
                           applicable, get_model_config, get_shape)
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import decode_cache_specs, default_runtime
from repro.parallel import hlo_analysis as HA
from repro.parallel import sharding as SH

#: per-arch training overrides chosen to fit v5e HBM (DESIGN.md §5)
TRAIN_OVERRIDES = {
    "deepseek-v2-236b": dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                             microbatches=16),
    "qwen1.5-32b": dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                        microbatches=8),
    "qwen2.5-32b": dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                        microbatches=8),
    "deepseek-7b": dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                        microbatches=4),
    "codeqwen1.5-7b": dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                           microbatches=4),
    "seamless-m4t-large-v2": dict(compute_dtype="bfloat16", microbatches=2),
    "granite-moe-1b-a400m": dict(compute_dtype="bfloat16", microbatches=2),
    "zamba2-1.2b": dict(compute_dtype="bfloat16", microbatches=4),
    "internvl2-1b": dict(compute_dtype="bfloat16", microbatches=2),
    "xlstm-125m": dict(compute_dtype="bfloat16", microbatches=2),
}

SERVE_DTYPE = "bfloat16"


def _tcfg(arch: str, kind: str) -> TrainConfig:
    if kind == "train":
        return TrainConfig(**TRAIN_OVERRIDES.get(arch, {}))
    return TrainConfig(param_dtype=SERVE_DTYPE, compute_dtype=SERVE_DTYPE)


def _moe_groups(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def _runtime(cfg, shape, mesh) -> dict:
    from jax.sharding import PartitionSpec as P
    rt = default_runtime(cfg, shape, moe_groups=_moe_groups(mesh))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    rt["act_spec"] = P(batch_axes, None, None)
    rt["logits_spec"] = P(batch_axes, None, "model")
    # GQA archs whose kv_heads don't divide the model axis: pin K/V
    # batch-sharded (gathered once per layer, not once per chunk)
    if shape.kind == "train":
        # save attention outputs across the layer scan: no S^2 recompute in
        # the backward pass at +1 residual-sized stash per layer (§Perf C)
        rt["remat_policy"] = "save_attn"
    msize = dict(mesh.shape).get("model", 1)
    if cfg.num_kv_heads % msize != 0 or cfg.num_kv_heads < msize:
        rt["kv_spec"] = P(batch_axes, None, None, None)
        if shape.kind == "decode" and shape.seq_len % msize == 0:
            # decode: keep the cache SHARDED over seq (distributed
            # flash-decoding) — never re-gather it per step
            rt["decode_kv_spec"] = P(batch_axes, "model", None, None)
    return rt


def analyze(lowered, compiled, cfg, shape, mesh) -> dict:
    from repro.parallel.hlo_cost import pattern_bytes, weighted_cost
    cost = compiled.cost_analysis()
    # trip-count-weighted re-walk of the HLO (lax.scan bodies count x trips;
    # XLA's cost_analysis counts them once — see parallel/hlo_cost.py)
    hlo_text = compiled.as_text()
    wc = weighted_cost(hlo_text)
    flops = wc["flops"]
    hbm = wc["bytes"]

    # flash-kernel adjustment (EXPERIMENTS.md §Perf): the HBM traffic inside
    # the "chunked_attention" scope is score-block round-tripping that the
    # validated Pallas kernel keeps in VMEM; its true HBM IO is q/k/v/o once.
    attn_bytes = pattern_bytes(hlo_text, "chunked_attention")
    flash_io = 0.0
    if attn_bytes and cfg.num_heads:
        n_dev = mesh.devices.size
        per_tensor = (shape.global_batch * shape.seq_len * cfg.num_heads *
                      cfg.resolved_head_dim * 2)
        layers = cfg.num_layers + (cfg.dec_layers if cfg.is_encdec else 0)
        flash_io = 4.0 * per_tensor * layers / n_dev
    hbm_flash = hbm - attn_bytes + flash_io
    coll = {k: float(v) for k, v in wc["collectives"].items()}
    total_coll = float(wc["collective_bytes"])
    n_dev = mesh.devices.size
    rl = HA.Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=total_coll,
                     num_devices=n_dev,
                     model_flops=HA.model_flops_for_cell(cfg, shape))
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
        # newer jaxlib dropped peak_memory_in_bytes; the CPU backend's temp
        # accounting is NOT a per-device HBM peak (it reports the whole
        # unoptimized buffer set), so peak_bytes is only emitted when the
        # backend reports a real peak — absent keys keep consumers'
        # .get(key, default) semantics meaningful
        mem_info = {k: v for k, v in mem_info.items() if v is not None}
    except Exception:                                      # CPU backend quirk
        mem_info = {}
    return {"roofline": rl.row(), "collectives": coll, "memory": mem_info,
            "hlo_flops": flops, "hlo_bytes": hbm,
            "attn_score_bytes": attn_bytes,
            "hlo_bytes_flash_adjusted": hbm_flash,
            "t_memory_flash_s": hbm_flash / HA.HBM_BW,
            "xla_flops_unweighted": float(cost.get("flops", 0.0)),
            "collective_bytes": total_coll, "num_devices": n_dev}


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                fl_round: bool = False, verbose: bool = True) -> dict:
    cfg = get_model_config(arch)
    shape = get_shape(shape_name)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    tcfg = _tcfg(arch, shape.kind)
    hcfg = HeliosConfig(enabled=shape.kind == "train",
                        contribution="grad_ema")
    rt = _runtime(cfg, shape, mesh)
    if shape.kind != "train":
        rt["act_spec"] = rt["logits_spec"] = None
    t0 = time.time()

    with mesh:
        if shape.kind == "train" and fl_round:
            n_clients = 2 if multi_pod else 1
            step = S.make_fl_round_step(cfg, hcfg, tcfg, rt, n_clients)
            state = S.abstract_fl_state(cfg, hcfg, tcfg, n_clients)
            in_sh = S.fl_state_shardings(cfg, state, mesh)
            batch = S.fl_abstract_batch(cfg, shape, tcfg, n_clients,
                                        local_steps=4)
            bsh = jax.tree.map(
                lambda l: jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(
                        "pod" if multi_pod else None, None,
                        "data" if l.shape[2] % 16 == 0 else None,
                        *([None] * (l.ndim - 3)))), batch)
            metr_abs = jax.eval_shape(step, state, batch)[1]
            jitted = jax.jit(step, in_shardings=(in_sh, bsh),
                             out_shardings=(in_sh,
                                            SH.replicated(metr_abs, mesh)))
            lowered = jitted.lower(state, batch)
        elif shape.kind == "train":
            step = S.make_train_step(cfg, hcfg, tcfg, rt)
            state = S.abstract_train_state(cfg, hcfg, tcfg)
            in_sh = S.train_state_shardings(cfg, state, mesh)
            batch = S.abstract_batch(cfg, shape, tcfg)
            bsh = SH.batch_shardings(batch, mesh, shape.global_batch)
            # new state keeps the input state's shardings (no replication)
            metr_abs = jax.eval_shape(step, state, batch)[1]
            jitted = jax.jit(step, in_shardings=(in_sh, bsh),
                             out_shardings=(in_sh,
                                            SH.replicated(metr_abs, mesh)),
                             donate_argnums=(0,))
            lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            step = S.make_prefill_step(cfg, rt)
            params = S.abstract_params_typed(cfg, tcfg)
            psh = SH.param_shardings(S.logical_axes(cfg), params, mesh,
                                     SH.rules_for(cfg))
            batch = S.abstract_batch(cfg, shape, tcfg)
            bsh = SH.batch_shardings(batch, mesh, shape.global_batch)
            # outputs: (logits, cache) — cache MUST be sharded or XLA
            # replicates seq_len x layers of KV per device (EXPERIMENTS.md
            # §Perf cell A)
            out_abs = jax.eval_shape(step, params, batch)
            osh = (SH.batch_shardings(out_abs[0], mesh, shape.global_batch),
                   SH.cache_shardings(out_abs[1], mesh, shape.global_batch,
                                      shape.seq_len, cfg.num_kv_heads))
            jitted = jax.jit(step, in_shardings=(psh, bsh),
                             out_shardings=osh)
            lowered = jitted.lower(params, batch)
        else:                                              # decode
            step = S.make_serve_step(cfg, rt)
            params = S.abstract_params_typed(cfg, tcfg)
            psh = SH.param_shardings(S.logical_axes(cfg), params, mesh,
                                     SH.rules_for(cfg, kind="decode"))
            cache = decode_cache_specs(cfg, shape, rt,
                                       param_dtype=S._dt(tcfg.param_dtype))
            csh = SH.cache_shardings(cache, mesh, shape.global_batch,
                                     shape.seq_len, cfg.num_kv_heads)
            token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            tsh = SH.batch_shardings(token, mesh, shape.global_batch)
            out_abs = jax.eval_shape(step, params, token, cache)
            osh = (SH.batch_shardings(out_abs[0], mesh, shape.global_batch),
                   SH.cache_shardings(out_abs[1], mesh, shape.global_batch,
                                      shape.seq_len, cfg.num_kv_heads))
            # donate the cache: in-place update, no double buffering
            jitted = jax.jit(step, in_shardings=(psh, tsh, csh),
                             out_shardings=osh, donate_argnums=(2,))
            lowered = jitted.lower(params, token, cache)

        compiled = lowered.compile()

    rec = {"arch": arch, "shape": shape_name, "status": "ok",
           "multi_pod": multi_pod, "fl_round": fl_round,
           "mesh": list(mesh.devices.shape),
           "compile_s": round(time.time() - t0, 1)}
    rec.update(analyze(lowered, compiled, cfg, shape, mesh))
    if verbose:
        r = rec["roofline"]
        print(f"[{arch} x {shape_name} x {'multi' if multi_pod else 'single'}"
              f"{' fl' if fl_round else ''}] compile={rec['compile_s']}s "
              f"bottleneck={r['bottleneck']} "
              f"t=(c {r['t_compute_s']:.3e}, m {r['t_memory_s']:.3e}, "
              f"x {r['t_collective_s']:.3e})s useful={r['useful_ratio']:.2f}",
              flush=True)
        print(f"  memory: {rec['memory']}", flush=True)
        print(f"  collectives: {rec['collectives']}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fl-round", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    records = []
    for arch, shape in cells:
        try:
            rec = dryrun_cell(arch, shape, multi_pod=args.multi_pod,
                              fl_round=args.fl_round)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
        records.append(rec)
        tag = ("multi" if args.multi_pod else "single") + \
            ("_fl" if args.fl_round else "")
        fname = os.path.join(args.out, f"{arch}_{shape}_{tag}.json")
        with open(fname, "w") as f:
            json.dump(rec, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(records)} cells")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

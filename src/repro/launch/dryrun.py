import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod);
  2. assembles ShapeDtypeStruct stand-ins (with NamedShardings attached) for
     every input of the step function -- params, optimizer state, batch, KV
     caches / SSM states -- NO device allocation anywhere;
  3. lowers + compiles train_step (train_4k), prefill_step (prefill_32k) or
     serve_step (decode_32k / long_500k);
  4. records memory_analysis(), cost_analysis(), and the collective-byte
     ledger parsed from the post-SPMD HLO into artifacts/dryrun/<cell>.json.

Shape-kind -> lowered step:
  train    -> training.steps.make_train_step (loss+grad+AdamW update)
  prefill  -> model.prefill  (full-seq forward + cache write)
  decode   -> model.decode_step (ONE token against a seq_len-sized cache)

Usage:
  python -m repro.launch.dryrun --arch minitron-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, get_config
from repro.launch.hlo_analysis import (parse_collectives, peaks_for,
                                       roofline_terms)
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.params import abstract_params, param_specs
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine
from repro.parallel.sharding import (ACTIVATION_RULES, batch_spec, spec_for)
from repro.training.steps import TrainState, make_train_step

TARGET_DEVICE_KIND = "TPU v5 lite"      # the pod make_production_mesh models

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(abstract_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, NamedSharding(mesh, s)),
        abstract_tree, spec_tree)


def _batch_sds(cfg, shape, mesh):
    specs = model_lib.input_specs(cfg, shape)
    out = {}
    for k, v in specs.items():
        spec = spec_for(v.shape, model_lib.batch_logical(cfg, shape)[k],
                        mesh, ACTIVATION_RULES)
        out[k] = _sds(v.shape, v.dtype, NamedSharding(mesh, spec))
    return out


def _params_sds(cfg, mesh):
    return _with_shardings(abstract_params(cfg), param_specs(cfg, mesh), mesh)


def _decode_state_sds(cfg, shape, mesh):
    ab = jax.eval_shape(
        lambda: model_lib.init_decode_state(cfg, shape.global_batch,
                                            shape.seq_len))
    logical = model_lib.decode_state_logical(cfg, ab)
    return jax.tree.map(
        lambda a, ax: _sds(a.shape, a.dtype, NamedSharding(
            mesh, spec_for(a.shape, ax, mesh, ACTIVATION_RULES))),
        ab, logical)


def cost_probe_plan(cfg):
    """UNROLLED small-depth variants whose HLO costs extrapolate linearly to
    the full depth. Needed because HloCostAnalysis counts a while-loop
    (lax.scan) body ONCE regardless of trip count, so the production scanned
    compile under-reports FLOPs/bytes/collectives by ~num_layers x.

    Returns (probes: {tag: cfg_variant}, combine: {tag: vec} -> vec) where
    vec is any per-device cost vector (flops, bytes, wire-bytes ...).
    """
    import dataclasses

    def mk(**kw):
        return dataclasses.replace(cfg, scan_layers=False, **kw)

    if cfg.family == "hybrid":
        from repro.models.transformer import hybrid_attn_layout
        k = cfg.attn_every
        _, _, n_attn = hybrid_attn_layout(cfg)
        probes = {"L1": mk(num_layers=1), "L2": mk(num_layers=2),
                  "Lk": mk(num_layers=k)}

        def combine(c):
            a = 2 * c["L1"] - c["L2"]
            bm = c["L2"] - c["L1"]
            ba = c["Lk"] - a - k * bm
            return a + cfg.num_layers * bm + n_attn * ba

        return probes, combine

    if cfg.family == "encdec":
        probes = {"E1D1": mk(encoder_layers=1, num_layers=1),
                  "E2D1": mk(encoder_layers=2, num_layers=1),
                  "E1D2": mk(encoder_layers=1, num_layers=2)}

        def combine(c):
            be = c["E2D1"] - c["E1D1"]
            bd = c["E1D2"] - c["E1D1"]
            a = c["E1D1"] - be - bd
            return a + cfg.encoder_layers * be + cfg.num_layers * bd

        return probes, combine

    probes = {"L1": mk(num_layers=1), "L2": mk(num_layers=2)}

    def combine(c):
        return 2 * c["L1"] - c["L2"] + (c["L2"] - c["L1"]) * cfg.num_layers

    return probes, combine


def _compile_cell(cfg, shape, mesh, **build_kw):
    """lower+compile one config; returns (compiled, lower_s, compile_s)."""
    t0 = time.time()
    fn, args = build_lowerable(cfg, shape, mesh, **build_kw)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, t_lower, time.time() - t0


def _cost_vector(compiled, n_dev):
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text(), n_dev)
    return (np.array([float(cost.get("flops", 0.0)),
                      float(cost.get("bytes accessed", 0.0)),
                      coll.wire_bytes]), coll)


def build_lowerable(cfg, shape, mesh, *, optimizer_name="adamw",
                    accum_steps=1, donate_state=False, sophia_kw=None):
    """Returns (fn, example_args) ready for jit(fn).lower(*args)."""
    rep = NamedSharding(mesh, P())

    if shape.kind == "train":
        if optimizer_name == "sophia_h":
            from repro.optim import sophia_h
            opt = sophia_h(warmup_cosine(3e-4, 100, 10_000),
                           **(sophia_kw or {}))
        else:
            opt = adamw(warmup_cosine(3e-4, 100, 10_000))
        p_sds = _params_sds(cfg, mesh)
        opt_abs = jax.eval_shape(opt.init, abstract_params(cfg))
        o_sds = {k: _with_shardings(v, param_specs(cfg, mesh), mesh)
                 for k, v in opt_abs.items()}
        state = TrainState(p_sds, o_sds, _sds((), jnp.int32, rep),
                           _sds((2,), jnp.uint32, rep))
        batch = _batch_sds(cfg, shape, mesh)
        step = make_train_step(cfg, mesh, opt, accum_steps=accum_steps)
        return step, (state, batch)

    p_sds = _params_sds(cfg, mesh)
    if shape.kind == "prefill":
        state = _decode_state_sds(cfg, shape, mesh)
        batch = _batch_sds(cfg, shape, mesh)

        def prefill_step(params, batch, state):
            return model_lib.prefill(params, cfg, batch, state, mesh)

        return jax.jit(prefill_step,
                       donate_argnums=(2,) if donate_state else ()), \
            (p_sds, batch, state)

    # decode: one token against a seq_len cache
    state = _decode_state_sds(cfg, shape, mesh)
    batch = _batch_sds(cfg, shape, mesh)

    def serve_step(params, tokens, pos, state):
        return model_lib.decode_step(params, cfg, tokens, pos, state, mesh)

    return jax.jit(serve_step,
                   donate_argnums=(3,) if donate_state else ()), \
        (p_sds, batch["tokens"], batch["pos"], state)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = ARTIFACT_DIR, force: bool = False,
             save: bool = True, variant: dict | None = None,
             tag: str = "") -> dict:
    """variant: §Perf overrides --
      {"cfg": {field: value, ...},            # ModelConfig perf knobs
       "accum_steps": int, "donate_state": bool,
       "optimizer": "sophia_h", "sophia_kw": {...}}
    """
    import dataclasses

    mesh_tag = "pod2" if multi_pod else "pod1"
    cell = f"{arch}__{shape_name}__{mesh_tag}" + (f"__{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    variant = variant or {}
    build_kw = {k: variant[k] for k in
                ("accum_steps", "donate_state", "optimizer_name",
                 "sophia_kw") if k in variant}
    if "optimizer" in variant:
        build_kw["optimizer_name"] = variant["optimizer"]

    cfg = get_config(arch)
    if variant.get("cfg"):
        cfg = dataclasses.replace(cfg, **variant["cfg"])
    shape = SHAPES[shape_name]
    from repro.configs.base import shape_supported
    ok, why = shape_supported(cfg, shape)
    rec = {"cell": cell, "arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": 512 if multi_pod else 256,
           "variant": {k: v for k, v in variant.items()}}
    if not ok:
        rec.update(status="skipped", reason=why)
        if save:
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    try:
        # 1) production compile (scan+remat): proves sharding/fit, gives
        #    memory_analysis + the collective schedule of the real step.
        compiled, t_lower, t_compile = _compile_cell(cfg, shape, mesh,
                                                     **build_kw)
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        scan_vec, coll = _cost_vector(compiled, n_dev)

        # 2) unrolled depth probes -> exact linear cost extrapolation
        #    (HloCostAnalysis counts scan bodies once; see cost_probe_plan).
        probes, combine = cost_probe_plan(cfg)
        probe_vecs = {}
        probe_times = {}
        for ptag, pcfg in probes.items():
            pc, _, pt = _compile_cell(pcfg, shape, mesh, **build_kw)
            probe_vecs[ptag], _ = _cost_vector(pc, n_dev)
            probe_times[ptag] = round(pt, 2)
            del pc
        total_vec = combine(probe_vecs)
        accum = build_kw.get("accum_steps", 1)
        if accum > 1:
            # the microbatch lax.scan body is also counted once by
            # HloCostAnalysis: scale to the full step (slightly overcounts
            # the once-per-step optimizer update; noted in §Perf)
            total_vec = total_vec * accum
        flops, bytes_, wire = (float(max(x, 0.0)) for x in total_vec)
        # the production mesh is a v5e pod: project onto its peaks, not
        # onto the host devices the dry run compiles for
        terms = roofline_terms(flops, bytes_, wire,
                               peaks_for(TARGET_DEVICE_KIND))

        mem_rec = {}
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    mem_rec[k] = int(v)

        rec.update(
            status="ok",
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            flops_per_device=flops, bytes_per_device=bytes_,
            collective_wire_bytes_per_device=wire,
            collective_ops=coll.ops, collective_bytes_by_kind=coll.by_kind,
            scan_body_once_cost={"flops": float(scan_vec[0]),
                                 "bytes": float(scan_vec[1]),
                                 "wire": float(scan_vec[2])},
            probe_costs={t: v.tolist() for t, v in probe_vecs.items()},
            probe_compile_s=probe_times,
            memory=mem_rec, roofline=terms,
            hlo_lines=len(hlo.splitlines()),
        )
        # model-FLOPs utilisation context (6*N*D for train, 2*N*D decode)
        N_active = cfg.active_params()
        if shape.kind == "train":
            model_flops = 6 * N_active * shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            model_flops = 2 * N_active * shape.global_batch * shape.seq_len
        else:
            model_flops = 2 * N_active * shape.global_batch
        rec["model_flops_total"] = float(model_flops)
        rec["model_flops_per_device"] = float(model_flops) / n_dev
        rec["useful_flop_ratio"] = (rec["model_flops_per_device"]
                                    / flops) if flops else None
    except Exception as e:  # noqa: BLE001 -- record the failure verbatim
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
    if save:
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    cells = []
    if args.all:
        for name, cfg, shape, ok, why in all_cells():
            cells.append((name, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            rec = run_cell(arch, shape_name, mp, args.out, args.force)
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f"bound={r['bound']}"
                         f" t=({r['compute_s']:.3e},{r['memory_s']:.3e},"
                         f"{r['collective_s']:.3e})s"
                         f" compile={rec['compile_s']}s")
                print(f"[{rec['cell']}] OK {extra}")
                if rec.get("memory"):
                    print(f"    memory: {rec['memory']}")
            elif status == "skipped":
                print(f"[{rec['cell']}] SKIP ({rec['reason'][:60]})")
            else:
                failures += 1
                print(f"[{rec['cell']}] ERROR {rec['error'][:200]}")
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh) cell:
  jax.jit(step, in_shardings=…).lower(**input_specs).compile()
must succeed on the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh.
Records memory_analysis / cost_analysis / collective schedule per cell into
artifacts/dryrun/*.json, with while-trip-count-corrected FLOPs/bytes/
collectives (see repro.launch.costing).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import costing
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, cell_runnable, input_specs,
                                 opt_spec, params_spec)
from repro.models.common import ModelConfig
from repro.models.scan_utils import cost_mode
from repro.models.transformer import (ShardCtx, apply_layer_decode,
                                      apply_layer_prefill, apply_layer_train)
from repro.optim.adamw import opt_pspecs
from repro.parallel.sharding import (batch_pspecs, cache_pspecs, named,
                                     param_pspecs, shard_ctx_for_mesh)
from repro.runtime.steps import make_prefill, make_serve_step, make_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")

#: sequence lengths for the sLSTM linear-cost fit (see costing docstring);
#: small because cost-mode unrolls S time steps per layer before the vjp
SLSTM_FIT_S = (32, 64)


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Standalone group-body compiles (trip-count correction)
# ---------------------------------------------------------------------------
def _group_param_shapes(p_shapes, gi: int):
    stacked = p_shapes["groups"][gi]
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                        stacked)


def _group_param_specs(p_specs, gi: int):
    stacked = p_specs["groups"][gi]
    return jax.tree.map(lambda s: P(*tuple(s)[1:]), stacked,
                        is_leaf=lambda x: isinstance(x, P))


def _body_cost(cfg: ModelConfig, ctx, mesh, kind: str, gi: int,
               p_shapes, p_specs, B: int, S: int, cache_shapes=None,
               cache_specs=None, exact: bool = False) -> costing.Cost:
    """Compile one group body standalone; return its Cost."""
    pattern, reps = cfg.blocks[gi]
    gp_shapes = _group_param_shapes(p_shapes, gi)
    gp_specs = _group_param_specs(p_specs, gi)
    dp = ctx.dp_axes
    import numpy as _np
    dp_size = int(_np.prod([mesh.shape[a] for a in dp]))
    if B % dp_size != 0:
        dp = ()   # tiny batch (long_500k): replicate over the dp axes
    dt = cfg.jdtype()
    x_sds = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    pos_sds = jax.ShapeDtypeStruct((B, S), jnp.int32)
    x_spec = P(dp, None, None) if dp else P(None, None, None)
    pos_spec = P(dp, None) if dp else P(None, None)

    if kind == "train":
        def fwd(x, positions, gp):
            for spec, p in zip(pattern, gp):
                x = apply_layer_train(cfg, spec, p, x, positions, ctx)
            return x

        # match the main program's remat policy so the bwd recompute FLOPs
        # are reflected in the corrected cost
        from repro.models.transformer import _remat
        fwd_r = _remat(cfg, lambda x, gp, positions: fwd(x, positions, gp))

        def body(x, positions, gp, ct):
            y, vjp = jax.vjp(lambda xx, pp: fwd_r(xx, pp, positions), x, gp)
            dx, dgp = vjp(ct)
            return y, dx, dgp

        args = (x_sds, pos_sds, gp_shapes, x_sds)
        shardings = (NamedSharding(mesh, x_spec), NamedSharding(mesh, pos_spec),
                     _named(mesh, gp_specs), NamedSharding(mesh, x_spec))
        fn = body
    elif kind == "prefill":
        def fn(x, positions, gp):
            outs = []
            for spec, p in zip(pattern, gp):
                x, st, _ = apply_layer_prefill(cfg, spec, p, x, positions, S,
                                               ctx)
                outs.append(st)
            return x, tuple(outs)

        args = (x_sds, pos_sds, gp_shapes)
        shardings = (NamedSharding(mesh, x_spec), NamedSharding(mesh, pos_spec),
                     _named(mesh, gp_specs))
    else:  # decode
        def fn(x, gp, gc, position):
            outs = []
            for spec, p, c in zip(pattern, gp, gc):
                x, nc, _ = apply_layer_decode(cfg, spec, p, x, c, position,
                                              ctx)
                outs.append(nc)
            return x, tuple(outs)

        gc_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            cache_shapes[gi])
        gc_specs = jax.tree.map(lambda s: P(*tuple(s)[1:]), cache_specs[gi],
                                is_leaf=lambda x: isinstance(x, P))
        args = (jax.ShapeDtypeStruct((B, 1, cfg.d_model), dt), gp_shapes,
                gc_shapes, jax.ShapeDtypeStruct((), jnp.int32))
        xd_spec = P(dp, None, None) if dp else P(None, None, None)
        shardings = (NamedSharding(mesh, xd_spec),
                     _named(mesh, gp_specs), _named(mesh, gc_specs),
                     NamedSharding(mesh, P()))

    def compile_once() -> costing.Cost:
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        return costing.cost_of_compiled(lowered.compile())

    if exact:
        with cost_mode():
            return compile_once()
    return compile_once()


def _has_slstm(cfg: ModelConfig, gi: int) -> bool:
    return any(s.kind == "slstm" for s in cfg.blocks[gi][0])


def corrected_cost(cfg: ModelConfig, ctx, mesh, kind: str, main_cost,
                   p_shapes, p_specs, B: int, S: int,
                   cache_shapes=None, cache_specs=None) -> costing.Cost:
    total = main_cost
    for gi, (pattern, reps) in enumerate(cfg.blocks):
        scan_cost = _body_cost(cfg, ctx, mesh, kind, gi, p_shapes, p_specs,
                               B, S, cache_shapes, cache_specs, exact=False)
        if kind == "decode":
            exact = scan_cost       # no inner loops in decode bodies
        elif _has_slstm(cfg, gi) and S > SLSTM_FIT_S[1]:
            s1, s2 = SLSTM_FIT_S
            c1 = _body_cost(cfg, ctx, mesh, kind, gi, p_shapes, p_specs,
                            B, s1, exact=True)
            c2 = _body_cost(cfg, ctx, mesh, kind, gi, p_shapes, p_specs,
                            B, s2, exact=True)
            slope = (c2 + c1.scale(-1.0)).scale(1.0 / (s2 - s1))
            exact = c1 + slope.scale(float(S - s1))
        else:
            exact = _body_cost(cfg, ctx, mesh, kind, gi, p_shapes, p_specs,
                               B, S, cache_shapes, cache_specs, exact=True)
        total = total + scan_cost.scale(-1.0) + exact.scale(float(reps))
    return total


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             correct: bool = True, cfg: Optional[ModelConfig] = None,
             save: bool = True) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    ok, why = cell_runnable(arch, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "skipped",
                           "reason": why}
    if not ok:
        return _save(out) if save else out

    cfg = cfg or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = shard_ctx_for_mesh(mesh)
    p_shapes = params_spec(cfg)
    p_specs = param_pspecs(cfg, p_shapes, mesh)
    t0 = time.time()

    cache_shapes = cache_specs = None
    if shape.kind == "train":
        o_shapes = opt_spec(cfg, p_shapes)
        o_specs = opt_pspecs(p_specs)
        b = input_specs(cfg, shape)
        b_specs = batch_pspecs(cfg, mesh)
        step = make_train_step(cfg, ctx)
        jitted = jax.jit(step,
                         in_shardings=(_named(mesh, p_specs),
                                       _named(mesh, o_specs),
                                       _named(mesh, b_specs)),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(p_shapes, o_shapes, b)
    elif shape.kind == "prefill":
        b = input_specs(cfg, shape)
        step = make_prefill(cfg, ctx, max_seq=shape.seq)
        dp = ctx.dp_axes
        jitted = jax.jit(step, in_shardings=(
            _named(mesh, p_specs), NamedSharding(mesh, P(dp))))
        lowered = jitted.lower(p_shapes, b["inputs"])
    else:  # decode
        specs = input_specs(cfg, shape)
        cache_shapes = specs["caches"]
        cache_specs = cache_pspecs(cfg, cache_shapes, mesh)
        dp = ctx.dp_axes
        import numpy as _np
        dp_size = int(_np.prod([mesh.shape[a] for a in dp]))
        tok_spec = P(dp) if shape.batch % dp_size == 0 else P(None)
        step = make_serve_step(cfg, ctx)
        jitted = jax.jit(step, in_shardings=(
            _named(mesh, p_specs), _named(mesh, cache_specs),
            NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())),
            donate_argnums=(1,))
        lowered = jitted.lower(p_shapes, cache_shapes, specs["tokens"],
                               specs["position"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = costing.memory_of_compiled(compiled)
    raw = costing.cost_of_compiled(compiled)
    out.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem,
        "raw": {"flops": raw.flops, "bytes": raw.bytes_accessed,
                "collectives": raw.coll},
    })
    print(compiled.memory_analysis())

    if correct:
        t0 = time.time()
        corr = corrected_cost(cfg, ctx, mesh, shape.kind, raw, p_shapes,
                              p_specs, shape.batch, shape.seq,
                              cache_shapes, cache_specs)
        out["corrected"] = {"flops": corr.flops, "bytes": corr.bytes_accessed,
                            "collectives": corr.coll}
        out["correct_s"] = round(time.time() - t0, 1)
    return _save(out) if save else out


def _save(out: Dict[str, Any]) -> Dict[str, Any]:
    os.makedirs(ARTIFACTS, exist_ok=True)
    fname = f"{out['arch']}__{out['shape']}__{out['mesh']}.json"
    with open(os.path.join(ARTIFACTS, fname), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-correct", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                fname = os.path.join(ARTIFACTS,
                                     f"{arch}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(fname):
                    prev = json.load(open(fname))
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[skip] {arch} {shape} {mesh_name} (cached)")
                        continue
                tag = f"{arch} {shape} {mesh_name}"
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    r = run_cell(arch, shape, mp,
                                 correct=not args.no_correct)
                    print(f"[done] {tag}: {r['status']} "
                          f"compile={r.get('compile_s')}s", flush=True)
                    results.append(r)
                except Exception as e:
                    traceback.print_exc()
                    _save({"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": str(e)[:2000]})
                    print(f"[FAIL] {tag}: {e}", flush=True)
    ok = sum(1 for r in results if r["status"] == "ok")
    print(f"\n{ok}/{len(results)} cells ok")


if __name__ == "__main__":
    main()

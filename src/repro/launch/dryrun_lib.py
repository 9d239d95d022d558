"""Dry-run machinery: lower + compile every (arch × shape × mesh) cell and
extract memory / FLOP / collective statistics for the roofline analysis.

Importable without touching jax device state — the 512-device XLA flag is
set by the thin ``dryrun.py`` entrypoint (and by tests with smaller
counts) *before* importing this module.
"""

from __future__ import annotations

import json
import re
import time
from pathlib import Path
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.dist import sharding as sh
from repro.launch import serve as serve_lib
from repro.launch import train as train_lib
from repro.launch.mesh import dp_size
from repro.models import LM
from repro.optim import adamw

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op in post-SPMD HLO."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            if f" {kind}(" not in line and f" {kind}-start(" not in line:
                continue
            lhs = line.split("=", 1)
            if len(lhs) != 2:
                continue
            total = 0.0
            for dt, dims in _SHAPE_RE.findall(lhs[1].split(kind)[0]):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += n * _DTYPE_BYTES[dt]
            out[kind] += total
            counts[kind] += 1
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


# ---------------------------------------------------------------------------

def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, or {} when the backend reports none."""
    return compiled.cost_analysis() or {}


def abstract_params(model: LM):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        return train_lib.train_batch_specs(cfg, shape)
    if shape.kind == "prefill":
        return serve_lib.prefill_specs(cfg, shape)
    return serve_lib.decode_specs(cfg, shape)


def apply_variant(cfg, variant: Optional[dict]):
    """Apply §Perf variant config overrides (act/fusion keys are handled
    by the lowering wrapper, the rest are ModelConfig fields)."""
    if not variant:
        return cfg
    import dataclasses
    fields = {k: v for k, v in variant.items()
              if k not in ("act", "fusion", "serve_params", "n_mb")}
    return dataclasses.replace(cfg, **fields) if fields else cfg


def resolve_layout(arch: str, shape_name: str, mesh,
                   variant: Optional[dict], layout: str) -> Optional[dict]:
    """``layout="auto"``: merge the searched layout (``dist/planner``)
    into the variant dict — explicit variant keys win, and any planner
    failure falls back to the PR-1 fixed rules (variant unchanged)."""
    if layout != "auto":
        return variant
    from repro.dist import planner
    cfg = apply_variant(get_config(arch), variant)
    return planner.auto_variant(mesh, cfg, SHAPES[shape_name], variant)


def lower_cell(arch: str, shape_name: str, mesh, *,
               fusion: str = "off",
               variant: Optional[dict] = None,
               layout: str = "fixed") -> tuple:
    """Build (jitted_fn, abstract args) for one cell on ``mesh``."""
    variant = resolve_layout(arch, shape_name, mesh, variant, layout)
    cfg = apply_variant(get_config(arch), variant)
    shape = SHAPES[shape_name]
    model = LM(cfg)
    params_abs = abstract_params(model)
    serve = bool(variant and variant.get("serve_params"))
    pspecs = sh.named(mesh, sh.param_specs(mesh, cfg, params_abs,
                                           serve=serve))

    if shape.kind == "train":
        dp = dp_size(mesh)
        n_mb = (variant or {}).get(
            "n_mb", train_lib.default_microbatches(cfg, shape, dp))
        tc = train_lib.TrainConfig(n_microbatches=n_mb, fusion=fusion)
        step = train_lib.make_train_step(model, cfg, tc)
        opt_abs = jax.eval_shape(
            lambda p: adamw.init(p, tc.opt), params_abs)
        ospecs = {"m": sh.param_specs(mesh, cfg, params_abs),
                  "v": sh.param_specs(mesh, cfg, params_abs),
                  "count": P()}
        batch_abs = train_lib.train_batch_specs(cfg, shape)
        bspecs = jax.tree_util.tree_map(
            lambda s: sh.batch_spec(mesh, cfg, s.shape[0],
                                    len(s.shape) - 1), batch_abs)
        jitted = jax.jit(step,
                         in_shardings=(pspecs, sh.named(mesh, ospecs),
                                       sh.named(mesh, bspecs)),
                         donate_argnums=(0, 1))
        return jitted, (params_abs, opt_abs, batch_abs)

    if shape.kind == "prefill":
        pre = serve_lib.make_prefill_step(model, cfg)
        cache_abs = serve_lib.cache_specs_abstract(model, shape)
        cspecs = sh.cache_specs(mesh, cfg, shape, cache_abs)
        batch_abs = serve_lib.prefill_specs(cfg, shape)
        bspecs = jax.tree_util.tree_map(
            lambda s: sh.batch_spec(mesh, cfg, s.shape[0],
                                    len(s.shape) - 1), batch_abs)

        def fn(params, tokens, cache, **kw):
            return pre(params, tokens, cache, **kw)

        args = dict(batch_abs)
        tokens_abs = args.pop("tokens")
        jitted = jax.jit(
            lambda params, tokens, cache: pre(params, tokens, cache),
            in_shardings=(pspecs, sh.named(mesh, bspecs["tokens"]),
                          sh.named(mesh, cspecs)),
            donate_argnums=(2,))
        return jitted, (params_abs, tokens_abs, cache_abs)

    # decode / long_decode
    step = serve_lib.make_serve_step(model, cfg)
    cache_abs = serve_lib.cache_specs_abstract(model, shape)
    cspecs = sh.cache_specs(mesh, cfg, shape, cache_abs)
    dspecs = serve_lib.decode_specs(cfg, shape)
    tok_spec = sh.batch_spec(mesh, cfg, shape.global_batch,
                             len(dspecs["token"].shape) - 1)
    jitted = jax.jit(step,
                     in_shardings=(pspecs, sh.named(mesh, cspecs),
                                   NamedSharding(mesh, tok_spec),
                                   NamedSharding(mesh, P())),
                     donate_argnums=(1,))
    return jitted, (params_abs, cache_abs, dspecs["token"], dspecs["pos"])


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, *,
             fusion: str = "off", save: bool = True,
             force: bool = False, variant: Optional[dict] = None,
             variant_tag: str = "", layout: str = "fixed") -> dict:
    """Lower + compile one cell; return (and persist) its statistics.
    ``layout="auto"`` lowers under the planner-searched layout."""
    tag = f"{arch}__{shape_name}__{mesh_name}" + (
        f"__fusion-{fusion}" if fusion != "off" else "") + (
        f"__{variant_tag}" if variant_tag else "") + (
        f"__layout-{layout}" if layout != "fixed" else "")
    out_path = RESULTS_DIR / f"{tag}.json"
    if save and out_path.exists() and not force:
        return json.loads(out_path.read_text())

    resolved = resolve_layout(arch, shape_name, mesh, variant, layout)
    # honesty marker: "auto" that fell back (or added nothing) lowers the
    # fixed baseline — record that so auto-vs-fixed comparisons can't
    # silently read baseline numbers as planner-searched results
    layout_applied = layout == "auto" and resolved != dict(variant or {})
    variant = resolved
    t0 = time.perf_counter()
    jitted, args = lower_cell(arch, shape_name, mesh, fusion=fusion,
                              variant=variant)
    import contextlib
    ctx = contextlib.nullcontext()
    if variant and variant.get("act"):
        from repro.dist.sharding import activation_rules
        ctx = activation_rules(mesh, variant["act"])
    with ctx:
        if isinstance(args, tuple):
            lowered = jitted.lower(*args)
        else:
            lowered = jitted.lower(**args)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from repro.launch.costing import corrected_collectives
    coll_corr = corrected_collectives(hlo)

    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "devices": n_dev, "fusion": fusion, "layout": layout,
        "layout_applied": layout_applied,
        "variant": variant_tag or "baseline",
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": coll,
        "collective_bytes_per_device_trip_corrected": coll_corr,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "time_lower_s": round(t_lower, 2),
        "time_compile_s": round(t_compile, 2),
    }
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1))
    return rec

"""Honest HLO cost accounting for scanned programs.

``compiled.cost_analysis()`` counts a ``while`` body **once**, so a
64-layer scanned model under-reports FLOPs by ~the trip count.  Two
complementary corrections:

1. **Probe extrapolation (FLOPs / bytes)** — lower *unrolled* miniature
   variants of the same cell (G ∈ {1,2} layer groups, M ∈ {1,2}
   microbatches, dense attention) on a small mesh and solve the affine
   model  f(G,M) = o₀ + o₁·G + M·(b + c·G)  for the per-group (c),
   per-microbatch (b) and optimizer (o₁,o₀) components, then evaluate at
   the production (G,M).  Costs that live inside *sequence* scans
   (Mamba/mLSTM cells, chunked-attention recompute) are added
   analytically — they are simple closed forms.

2. **Trip-corrected collectives** — parse the *production* compiled HLO,
   build the computation call graph, multiply each while body's
   collective bytes by its trip count (read from the loop condition's
   bound constant).

Everything is derived from compiled artifacts of the real programs; no
wall-clock measurement is involved (CPU container, TPU target).
"""

from __future__ import annotations

import json
import re
from dataclasses import replace
from pathlib import Path

import jax

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.lm import build_pattern

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "costing"


# ---------------------------------------------------------------------------
# trip-corrected collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?(%?[\w\.\-]+)\s*\(.*\)\s*->.*{")
_WHILE_RE = re.compile(r"while\(.*?\).*?condition=(%?[\w\.\-]+),"
                       r"\s*body=(%?[\w\.\-]+)", re.S)
#: non-while call edges only — while body/condition are handled with trip
#: counts by _WHILE_RE (listing them here would double count).
_CALL_RE = re.compile(r"(?:calls|to_apply|branch_computations)="
                      r"(%?[\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _result_bytes(lhs: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(lhs):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_hlo(hlo: str):
    """computations: name -> {lines}, whiles per computation, trip counts."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and "->" in line and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def trip_count(comps: dict, cond_name: str) -> int:
    lines = comps.get(cond_name, [])
    consts = [int(c) for l in lines for c in _CONST_RE.findall(l)]
    return max(consts) if consts else 1


def corrected_collectives(hlo: str) -> dict:
    """Per-kind collective bytes with while bodies multiplied by trips."""
    comps = parse_hlo(hlo)
    direct: dict[str, dict] = {}
    children: dict[str, list[tuple[str, int]]] = {}
    for name, lines in comps.items():
        d = {k: 0.0 for k in _COLLECTIVES}
        ch: list[tuple[str, int]] = []
        for line in lines:
            for kind in _COLLECTIVES:
                if f" {kind}(" in line or f" {kind}-start(" in line:
                    parts = line.split("=", 1)
                    if len(parts) == 2:
                        d[kind] += _result_bytes(parts[1].split(kind)[0])
                    break
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                ch.append((body, trip_count(comps, cond)))
            else:
                for callee in _CALL_RE.findall(line):
                    if callee in comps:
                        ch.append((callee, 1))
        direct[name] = d
        children[name] = ch

    memo: dict[str, dict] = {}

    def effective(name: str, depth=0) -> dict:
        if name in memo:
            return memo[name]
        if depth > 50:
            return {k: 0.0 for k in _COLLECTIVES}
        acc = dict(direct.get(name, {k: 0.0 for k in _COLLECTIVES}))
        for callee, trips in children.get(name, []):
            sub = effective(callee, depth + 1)
            for k in _COLLECTIVES:
                acc[k] += trips * sub[k]
        memo[name] = acc
        return acc

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line.strip())
            if m:
                entry = m.group(1)
            break
    res = effective(entry) if entry else {k: 0.0 for k in _COLLECTIVES}
    res["total"] = sum(res[k] for k in _COLLECTIVES)
    return res


# ---------------------------------------------------------------------------
# probe extrapolation for FLOPs / bytes
# ---------------------------------------------------------------------------

def _probe_cfg(cfg: ModelConfig, n_groups: int) -> ModelConfig:
    P = len(build_pattern(cfg))
    return replace(cfg, n_layers=n_groups * P, scan_layers=False,
                   attn_chunk=0)


def _measure(cfg, shape, mesh, n_mb: int) -> tuple[float, float]:
    """(total flops, total bytes) of one probe variant."""
    from repro.launch import dryrun_lib as dl
    from repro.launch import serve as serve_lib
    from repro.launch import train as train_lib
    from repro.dist import sharding as sh
    from repro.models import LM
    from repro.optim import adamw
    from jax.sharding import NamedSharding, PartitionSpec as P_

    model = LM(cfg)
    params_abs = dl.abstract_params(model)
    pspecs = sh.named(mesh, sh.param_specs(mesh, cfg, params_abs))
    if shape.kind == "train":
        tc = train_lib.TrainConfig(n_microbatches=n_mb, unroll_mb=True)
        step = train_lib.make_train_step(model, cfg, tc)
        opt_abs = jax.eval_shape(lambda p: adamw.init(p, tc.opt), params_abs)
        ospecs = {"m": sh.param_specs(mesh, cfg, params_abs),
                  "v": sh.param_specs(mesh, cfg, params_abs),
                  "count": P_()}
        batch_abs = train_lib.train_batch_specs(cfg, shape)
        bspecs = jax.tree_util.tree_map(
            lambda s: sh.batch_spec(mesh, cfg, s.shape[0],
                                    len(s.shape) - 1), batch_abs)
        comp = jax.jit(step, in_shardings=(
            pspecs, sh.named(mesh, ospecs), sh.named(mesh, bspecs))
        ).lower(params_abs, opt_abs, batch_abs).compile()
    elif shape.kind == "prefill":
        pre = serve_lib.make_prefill_step(model, cfg)
        cache_abs = serve_lib.cache_specs_abstract(model, shape)
        cspecs = sh.cache_specs(mesh, cfg, shape, cache_abs)
        batch_abs = serve_lib.prefill_specs(cfg, shape)
        tspec = sh.batch_spec(mesh, cfg, shape.global_batch,
                              len(batch_abs["tokens"].shape) - 1)
        comp = jax.jit(lambda p, t, c: pre(p, t, c), in_shardings=(
            pspecs, NamedSharding(mesh, tspec), sh.named(mesh, cspecs))
        ).lower(params_abs, batch_abs["tokens"], cache_abs).compile()
    else:
        step = serve_lib.make_serve_step(model, cfg)
        cache_abs = serve_lib.cache_specs_abstract(model, shape)
        cspecs = sh.cache_specs(mesh, cfg, shape, cache_abs)
        dspecs = serve_lib.decode_specs(cfg, shape)
        tspec = sh.batch_spec(mesh, cfg, shape.global_batch,
                              len(dspecs["token"].shape) - 1)
        comp = jax.jit(step, in_shardings=(
            pspecs, sh.named(mesh, cspecs), NamedSharding(mesh, tspec),
            NamedSharding(mesh, P_()))
        ).lower(params_abs, cache_abs, dspecs["token"],
                dspecs["pos"]).compile()
    ca = dl.cost_analysis_dict(comp)
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    return (float(ca.get("flops", 0.0)) * n_dev,
            float(ca.get("bytes accessed", 0.0)) * n_dev)


def _seq_scan_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic FLOPs living inside sequence scans (counted once by the
    probes): Mamba/mLSTM cell steps and chunked-attention recompute."""
    pattern = build_pattern(cfg)
    L = cfg.n_layers
    per = len(pattern)
    if shape.kind == "train":
        B, S = shape.global_batch, shape.seq_len
        bwd_mult = 3.0       # fwd + ~2x bwd (scan body differentiated)
    elif shape.kind == "prefill":
        B, S = shape.global_batch, shape.seq_len
        bwd_mult = 1.0
    else:
        return 0.0           # decode: single step, fully counted

    total = 0.0
    n_mamba = sum(s.kind == "mamba" for s in pattern) * (L // per)
    n_mlstm = sum(s.kind == "mlstm" for s in pattern) * (L // per)
    n_attn = sum(s.kind == "attn" for s in pattern) * (L // per)
    if n_mamba:
        di, N = cfg.ssm_expand * cfg.d_model, cfg.ssm_state
        total += n_mamba * B * S * di * N * 26.0 * bwd_mult
    if n_mlstm:
        di = cfg.ssm_expand * cfg.d_model
        hd = di // cfg.n_heads
        total += n_mlstm * B * S * cfg.n_heads * hd * hd * 5.5 * bwd_mult
    if shape.kind == "train" and cfg.attn_chunk and n_attn:
        # chunk-body remat: one extra attention forward in the backward
        # (0.5 ≈ causal-mask effective score density)
        for s in pattern:
            if s.kind != "attn":
                continue
            s_eff = min(s.window or S, S)
            total += (L // per) * 4.0 * B * S * s_eff \
                * cfg.n_heads * cfg.hd * 0.5
    return total


def probe_cell(arch: str, shape_name: str, probe_mesh, *,
               save: bool = True, force: bool = False,
               variant: dict | None = None, variant_tag: str = "") -> dict:
    """Extrapolated total (flops, bytes) for the production cell."""
    tag = f"{arch}__{shape_name}" + (f"__{variant_tag}" if variant_tag
                                     else "")
    out_path = RESULTS_DIR / f"{tag}.json"
    if save and out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    if variant:
        from repro.launch.dryrun_lib import apply_variant
        cfg = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    P = len(build_pattern(cfg))
    G_full = cfg.n_layers / P

    if shape.kind == "train":
        from repro.launch import train as train_lib
        f = {}
        b = {}
        for (g, m) in ((1, 1), (2, 1), (1, 2), (2, 2)):
            f[(g, m)], b[(g, m)] = _measure(_probe_cfg(cfg, g), shape,
                                            probe_mesh, m)

        def extrap(v):
            c = v[(2, 2)] - v[(2, 1)] - v[(1, 2)] + v[(1, 1)]
            bb = v[(1, 2)] - v[(1, 1)] - c
            o1 = v[(2, 1)] - v[(1, 1)] - c
            o0 = v[(1, 1)] - o1 - bb - c
            M = train_lib.default_microbatches(cfg, shape, 16)
            return o0 + o1 * G_full + M * (bb + c * G_full)

        flops, bytes_ = extrap(f), extrap(b)
    else:
        f1, b1 = _measure(_probe_cfg(cfg, 1), shape, probe_mesh, 1)
        f2, b2 = _measure(_probe_cfg(cfg, 2), shape, probe_mesh, 1)
        cf, cb = f2 - f1, b2 - b1
        flops = (f1 - cf) + cf * G_full
        bytes_ = (b1 - cb) + cb * G_full

    flops += _seq_scan_flops(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "total_flops": flops, "total_bytes": bytes_}
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    """Probe every live cell (run under a small host-device count)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    from repro.configs import all_configs, cells
    probe_mesh = jax.make_mesh(
        (4, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch, shape in cells(all_configs()):
        try:
            rec = probe_cell(arch, shape, probe_mesh, force=args.force)
            print(f"OK   {arch:18s} {shape:12s} "
                  f"flops={rec['total_flops']:.3e} "
                  f"bytes={rec['total_bytes']:.3e}", flush=True)
        except Exception as e:
            print(f"FAIL {arch} {shape}: {type(e).__name__}: {e}",
                  flush=True)


if __name__ == "__main__":
    main()

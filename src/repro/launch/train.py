"""Training step factory + CLI driver.

``make_train_step`` builds the jit-able global-SPMD step: microbatch
gradient accumulation (lax.scan; the scan body is also where XLA's
latency-hiding scheduler overlaps FSDP all-gathers with compute), fused
softmax-CE loss through the paper's planner (Row template) when
``fusion`` is enabled, AdamW update on fully-sharded state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import LM, lm_loss
from repro.models.lm import N_PATCHES
from repro.optim import adamw


@dataclass(frozen=True)
class TrainConfig:
    n_microbatches: int = 1
    moe_aux_weight: float = 0.01
    fusion: str = "off"          # off | gen | fa | fnr  (planner arm)
    unroll_mb: bool = False      # python-loop microbatches (cost probes)
    #: mesh or FusionLayout for the fused-loss planner: the LSE Row chain
    #: iterates flattened (B·S) token rows, so under a layout the planner
    #: may place it distributed (row-partitioned, no collective) while the
    #: rest of the step stays under GSPMD.  None keeps local planning.
    fusion_layout: Optional[object] = None
    #: whole-plan staged execution of the fused loss (False: per-operator
    #: dispatch — the debug path; see repro.core.codegen.CompiledPlan)
    fusion_staged: bool = True
    opt: adamw.OptConfig = adamw.OptConfig()


def _fused_lse(logits2d: jnp.ndarray, mode: str,
               layout=None, staged: bool = True) -> jnp.ndarray:
    """log-sum-exp rows through the fusion planner (Row template:
    rowmax → sub → exp → rowsums → log → add), staged explicitly:
    trace → plan → compile once per (shape, mode, layout, staged), then
    reuse the Compiled operator — whole-plan jitted by default
    (``staged=False`` keeps per-operator dispatch for debugging).
    Differentiable: the training backward pass runs the planned gradient
    DAG via the operator's custom_vjp."""
    from repro.core import fused, ir
    from repro.core.layout import layout_signature

    if not hasattr(_fused_lse, "_lse"):
        @fused
        def _lse(L):
            m = L.rowmaxs()
            return ir.log(ir.exp(L - m).rowsums()) + m
        _fused_lse._lse = _lse
        _fused_lse._ops = {}
    key = (tuple(logits2d.shape), mode, layout_signature(layout), staged)
    op = _fused_lse._ops.get(key)
    if op is None:
        op = _fused_lse._lse.trace(logits2d) \
                            .plan(mode=mode, layout=layout) \
                            .compile(staged=staged)
        _fused_lse._ops[key] = op
    return op(logits2d)


def make_loss_fn(model: LM, cfg: ModelConfig, tc: TrainConfig):
    def loss_fn(params, batch):
        prefix = batch.get("patches")
        logits, _, aux = model.apply(params, batch["tokens"],
                                     prefix_emb=prefix)
        if prefix is not None:
            logits = logits[:, prefix.shape[1]:]
        targets = batch["targets"]
        if cfg.n_codebooks > 1:
            ce = jnp.mean(jnp.stack(
                [_ce(logits[..., c, :], targets[..., c], tc)
                 for c in range(cfg.n_codebooks)]))
        else:
            ce = _ce(logits, targets, tc)
        return ce + tc.moe_aux_weight * aux, ce
    return loss_fn


def _ce(logits, targets, tc: TrainConfig):
    if tc.fusion == "off":
        return lm_loss(logits, targets)
    V = logits.shape[-1]
    flat = logits.reshape(-1, V).astype(jnp.float32)
    lse = _fused_lse(flat, tc.fusion, layout=tc.fusion_layout,
                     staged=tc.fusion_staged)
    tgt = jnp.take_along_axis(flat, targets.reshape(-1, 1), axis=-1)
    return jnp.mean(lse - tgt)


def make_train_step(model: LM, cfg: ModelConfig, tc: TrainConfig):
    loss_fn = make_loss_fn(model, cfg, tc)

    def train_step(params, opt_state, batch):
        n_mb = tc.n_microbatches

        def split(x):
            return x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:])

        mbs = jax.tree_util.tree_map(split, batch)
        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            g_acc, loss_acc = acc
            (_, ce), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + ce), None

        if n_mb > 1 and tc.unroll_mb:
            acc = (zero, 0.0)
            for i in range(n_mb):
                mb = jax.tree_util.tree_map(lambda x: x[i], mbs)
                acc, _ = body(acc, mb)
            grads, loss_sum = acc
            grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)
            loss = loss_sum / n_mb
        elif n_mb > 1:
            (grads, loss_sum), _ = jax.lax.scan(body, (zero, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)
            loss = loss_sum / n_mb
        else:
            (_, loss), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, metrics = adamw.update(grads, opt_state,
                                                    params, tc.opt)
        metrics = dict(metrics, loss=loss)
        return new_params, new_opt, metrics

    return train_step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — shared with the dry-run)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend == "vision":
        S = S - N_PATCHES            # total context = patches + tokens
    tok_shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B, S)
    specs = {
        "tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
        "targets": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
    }
    if cfg.frontend == "vision":
        specs["patches"] = jax.ShapeDtypeStruct(
            (B, N_PATCHES, cfg.d_model), jnp.bfloat16)
    return specs


def default_microbatches(cfg: ModelConfig, shape: ShapeConfig,
                         dp: int) -> int:
    """Pick accumulation depth so per-microbatch activations fit HBM while
    the microbatch still shards over the data axes."""
    total = cfg.total_params
    want = 8 if total > 1e11 else (4 if total > 2e10 else 2)
    return max(1, min(want, shape.global_batch // dp))


# ---------------------------------------------------------------------------
# CLI driver: end-to-end training on the local host mesh
# ---------------------------------------------------------------------------

def main() -> None:
    import argparse
    from dataclasses import replace

    from repro import compile_cache
    from repro.checkpoint import CheckpointStore
    from repro.data import DataConfig, ShardedLoader
    from repro.dist import sharding as sh
    from repro.launch.mesh import make_host_mesh
    from repro.train import LoopConfig, run_loop

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-1.3b")
    ap.add_argument("--preset", default="tiny",
                    choices=("tiny", "100m", "full"))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--fusion", default="off")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    from repro.configs import get_config
    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = cfg.reduced()
    elif args.preset == "100m":
        cfg = replace(cfg.reduced(), n_layers=8, d_model=512, n_heads=8,
                      n_kv_heads=min(8, max(1, cfg.n_kv_heads)),
                      head_dim=64, d_ff=2048 if cfg.d_ff else 0,
                      vocab=32_000)
    model = LM(cfg)
    mesh = make_host_mesh()
    params = model.init(jax.random.PRNGKey(0))
    pspecs = sh.named(mesh, sh.param_specs(mesh, cfg, params))
    params = jax.tree_util.tree_map(jax.device_put, params, pspecs)

    tc = TrainConfig(n_microbatches=1, fusion=args.fusion)
    opt_state = adamw.init(params, tc.opt)
    step_fn = jax.jit(make_train_step(model, cfg, tc),
                      donate_argnums=(0, 1))

    store = CheckpointStore(args.ckpt_dir)
    start = 0
    if args.resume and store.latest_step() is not None:
        tree, extra = store.restore({"params": params, "opt": opt_state})
        params, opt_state, start = tree["params"], tree["opt"], extra["step"]
        print(f"resumed from step {start}")

    loader = ShardedLoader(
        DataConfig(seq_len=args.seq, global_batch=args.batch,
                   vocab=cfg.vocab, n_codebooks=cfg.n_codebooks),
        start_step=start)
    cfg_loop = LoopConfig(total_steps=args.steps,
                          checkpoint_every=args.ckpt_every, log_every=5)

    def log(step, loss, dt, metrics):
        print(f"step {step:5d} loss {loss:.4f} "
              f"({dt * 1e3:.0f} ms/step)", flush=True)

    params, opt_state, st = run_loop(step_fn, params, opt_state, loader,
                                     cfg_loop, store=store,
                                     start_step=start, on_metrics=log)
    loader.close()
    print(f"done: {st.step} steps, final loss "
          f"{st.losses[-1] if st.losses else float('nan'):.4f}, "
          f"stragglers={len(st.straggler_events)}, "
          f"skipped={len(st.skipped_steps)}")


if __name__ == "__main__":
    main()

"""Pallas TPU skeleton for the **MAgg** (multi-aggregate) template.

k full aggregates over shared inputs evaluate in a single pass: one grid
over the shared main input's tiles, k program roots interpreted on the same
resident tiles, k accumulators in a (k,1) output block (paper Fig. 1(c):
sum(X⊙Y), sum(X⊙Z), sum(X²) share one scan of X).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core.cplan import CPlan
from . import ref
from .cellwise import cell_blocks, _tile_spec, _COMB


def multiagg_pallas(cplan: CPlan, env: dict[int, jnp.ndarray], *,
                    interpret: bool = False) -> jnp.ndarray:
    main = env[cplan.main.nid]
    m, n = main.shape
    bm, bn, _ = cell_blocks(
        cplan, {b.nid: env[b.nid].shape for b in cplan.binds})

    roots = [cplan.prog_root] + [r for r, _ in cplan.extra]
    aggs = [cplan.agg_op] + [op for _, op in cplan.extra]
    k = len(roots)

    binds = list(cplan.binds)
    arrays = [jnp.asarray(env[b.nid]) for b in binds]
    dtype = arrays[0].dtype
    in_specs = [_tile_spec(a.shape, m, n, bm, bn, False) for a in arrays]
    nid_to_pos = {b.nid: i for i, b in enumerate(binds)}

    def kernel(*refs):
        *ins, out = refs
        read = lambda nid: ins[nid_to_pos[nid]][...]
        vals = ref.apply_program(cplan, read, roots)
        parts = [jnp.sum(v) if a in ("sum", "mean") else
                 (jnp.min(v) if a == "min" else jnp.max(v))
                 for v, a in zip(vals, aggs)]
        part = jnp.stack(parts).reshape(k, 1).astype(dtype)
        first = jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0)

        @pl.when(first)
        def _init():
            out[...] = part

        @pl.when(jnp.logical_not(first))
        def _acc():
            old = out[...]
            new = [jnp.asarray(_COMB[a](old[i, 0], part[i, 0]))
                   for i, a in enumerate(aggs)]
            out[...] = jnp.stack(new).reshape(k, 1)

    out = pl.pallas_call(
        kernel, grid=(m // bm, n // bn), in_specs=in_specs,
        out_specs=pl.BlockSpec((k, 1), lambda o, i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), dtype),
        interpret=interpret,
        name=obs.kernel_name("magg", cplan.variant,
                             cplan.cache_key()))(*arrays)
    scale = jnp.array([[1.0 / (m * n)] if a == "mean" else [1.0]
                       for a in aggs], dtype)
    return out * scale

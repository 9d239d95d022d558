"""Layout rules for the hybrid local/distributed execution stack.

The planner costs fusion plans across *local and distributed* operators
(companion work: costing generated runtime plans), which requires knowing
how every tensor of a cell is laid out on the production mesh before
anything is compiled.  This module is that knowledge: pure, mesh-shaped
functions from abstract leaves to ``PartitionSpec`` trees.  Everything
validates abstractly — no device allocation, no compilation — so the
dry-run can cost 256/512-device pods from a CPU container.

Conventions
-----------
* ``mesh`` only needs ``.shape`` (axis name → size mapping) and
  ``.axis_names``; tests pass a lightweight stand-in.
* The tensor-parallel (TP) axis is named ``"model"``; every other mesh
  axis (``"data"``, ``"pod"``, …) is an FSDP/data axis.
* Rules degrade gracefully: an axis that is absent from the mesh or
  does not divide a dimension is dropped (that dim replicates) — never
  an error.  Within a multi-axis FSDP group, axes are dropped
  left-to-right (``"pod"`` before ``"data"``) until the rest divides.

Parameter layout (megatron-style TP × FSDP)
-------------------------------------------
* Projections *into* head/ff space (``wq``/``wk``/``wv``, dense
  ``w1``/``w3``, ``up``, ``in_proj``) shard their output dim over TP and
  their ``d_model`` dim over FSDP; projections *out of* it (``wo``,
  dense ``w2``, ``down``, ``out_proj``) are the transpose.
* Embedding shards the vocab over TP (vocab-parallel logits) and
  ``d_model`` over FSDP; an untied ``head`` is the transpose.
* MoE expert weights shard the **expert** dim over TP when the expert
  count divides it (expert parallelism — olmoe's 64/16), else fall back
  to ff-TP (grok's 8 experts on a 16-way axis).  The same predicate
  (:func:`moe_expert_parallel`) gates the ``shard_map`` all-to-all
  dispatch in ``models/moe.py``.
* Stacked leaves (the scanned ``blocks`` pytrees carry a leading layer-
  group dim) replicate every leading dim the rule doesn't name: rules
  are aligned to the *trailing* dims of each leaf.
* ``serve=True`` drops the FSDP axes (decode reads weights every step;
  all-gathering them each token is the wrong side of the roofline) and
  keeps TP.

Activation layouts are keyed by short strings (``"btd"``, ``"bthd"``,
``"btf"``, ``"btv"``) and only apply inside the
:func:`activation_rules` context — outside it :func:`constrain` is an
identity, so model code is importable and traceable with no mesh at all.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

TP_AXIS = "model"


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def tp_axis(mesh) -> Optional[str]:
    """The tensor-parallel axis name, or None if the mesh has none."""
    return TP_AXIS if TP_AXIS in mesh.axis_names else None


def fsdp_axes(mesh) -> tuple[str, ...]:
    """Every mesh axis except the tensor-parallel one, mesh order."""
    return tuple(a for a in mesh.axis_names if a != TP_AXIS)


def axis_size(mesh, axes) -> int:
    """Product of mesh-axis sizes for a None/str/tuple spec entry."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _fit(mesh, dim: int, axes):
    """Largest suffix of ``axes`` that exists in the mesh and divides
    ``dim`` — the graceful-degradation primitive.  Returns a spec entry
    (None / str / tuple)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    while axes:
        n = axis_size(mesh, axes)
        if n > 1 and dim % n == 0:
            return axes[0] if len(axes) == 1 else axes
        axes = axes[1:]
    return None


def _spec(mesh, shape: tuple, roles: tuple) -> P:
    """Build a rank-matched PartitionSpec from per-dim axis requests.

    ``roles`` aligns to the *trailing* dims of ``shape``; leading
    (stacked) dims replicate.  Each entry is divisibility-checked
    against its dim and degrades to None via :func:`_fit`."""
    pad = len(shape) - len(roles)
    if pad < 0:
        return P()
    entries = [None] * pad + [_fit(mesh, d, r)
                              for d, r in zip(shape[pad:], roles)]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _path_keys(path) -> list:
    keys = []
    for entry in path:
        if hasattr(entry, "key"):
            keys.append(entry.key)
        elif hasattr(entry, "idx"):
            keys.append(entry.idx)
        else:
            keys.append(str(entry))
    return keys


# ---------------------------------------------------------------------------
# expert parallelism
# ---------------------------------------------------------------------------

def operand_spec(mesh, shape) -> P:
    """Layout rule for one fused-operator operand (the fusion planner's
    ``FusionLayout.auto``): rows over the FSDP axes, columns over the TP
    axis, with the usual per-dim divisibility degradation — so a (1, n)
    row vector or a matrix whose rows don't divide the data axes simply
    replicates.  This is the spec tree the hybrid local/distributed
    placement (``repro.core.cost.DistParams``) reads its row/column shard
    factors from."""
    return _spec(mesh, tuple(shape), (fsdp_axes(mesh), tp_axis(mesh)))


def moe_expert_parallel(mesh, cfg) -> bool:
    """True when expert weights shard over the TP axis (EP): the expert
    count must be a positive multiple of the axis size.  olmoe (64e) on a
    16-way axis → EP; grok (8e) → ff-TP fallback."""
    tp = tp_axis(mesh)
    return (tp is not None and cfg.n_experts > 0
            and cfg.n_experts % mesh.shape[tp] == 0)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _param_roles(name: Any, base_rank: int, F, tp, ep: bool):
    """Trailing-dim axis requests for one named parameter leaf.

    ``F`` is the FSDP axis group (or None for the serving layout); ``tp``
    the TP axis (or None).  Unknown leaves (norm scales, gate biases,
    SSM vectors) replicate."""
    if name == "embed":                       # (V, d) / (nc, V, d)
        return (tp, F)
    if name == "head":                        # (d, V) / (d, nc·V)
        return (F, tp)
    if name in ("wq", "wk", "wv", "up", "in_proj"):
        return (F, tp)                        # (d_in, heads/ff·…)
    if name in ("wo", "out_proj", "down"):
        return (tp, F)                        # (heads/ff·…, d_out)
    if name in ("w1", "w3"):
        if base_rank == 3:                    # MoE (e, d, f)
            return (tp, F, None) if ep else (None, F, tp)
        return (F, tp)                        # dense (d, f)
    if name == "w2":
        if base_rank == 3:                    # MoE (e, f, d)
            return (tp, None, F) if ep else (None, tp, F)
        return (tp, F)                        # dense (f, d)
    if name == "router":                      # (d, e) — e is tiny
        return (F, None)
    if name == "x_proj":                      # (di, 2N+1)
        return (tp, None)
    if name == "A_log":                       # (di, N)
        return (tp, None)
    if name == "conv_w":                      # (K, di)
        return (None, tp)
    if name == "wif":                         # (di, 2H)
        return (F, None)
    return ()


def param_specs(mesh, cfg, params, *, serve: bool = False,
                moe: Optional[str] = None):
    """PartitionSpec tree mirroring ``params`` (the ``LM.init`` tree).

    Every spec is rank-matched and divisibility-checked against its
    abstract leaf; ``serve=True`` drops the FSDP axes (TP only).
    ``moe`` forces the expert-weight role (``"ep"`` / ``"fftp"``) instead
    of the :func:`moe_expert_parallel` predicate — the layout planner
    costs both roles; ``None`` keeps the fixed rule."""
    F = None if serve else (fsdp_axes(mesh) or None)
    tp = tp_axis(mesh)
    ep = moe_expert_parallel(mesh, cfg) if moe is None else (moe == "ep")

    def rule(path, leaf):
        keys = _path_keys(path)
        stacked = bool(keys) and keys[0] == "blocks"
        name = keys[-1] if keys else None
        shape = tuple(leaf.shape)
        base_rank = len(shape) - 1 if stacked else len(shape)
        return _spec(mesh, shape, _param_roles(name, base_rank, F, tp, ep))

    return jax.tree_util.tree_map_with_path(rule, params)


# ---------------------------------------------------------------------------
# decode-cache specs
# ---------------------------------------------------------------------------

#: trailing-dim axis requests per cache leaf name (leading stacked layer-
#: group dims replicate).  "F"/"tp" placeholders resolved per mesh.
_CACHE_ROLES = {
    "k":    ("F", None, "tp", None),    # (B, S, KV, hd) — heads over TP
    "v":    ("F", None, "tp", None),
    "h":    ("F", "tp", None),          # mamba (B, di, N)
    "conv": ("F", None, "tp"),          # mamba (B, K-1, di)
    "C":    ("F", "tp", None, None),    # mlstm (B, H, hd, hd)
    "n":    ("F", "tp", None),          # mlstm (B, H, hd)
    "m":    ("F", "tp"),                # mlstm (B, H)
}


def cache_specs(mesh, cfg, shape, cache):
    """PartitionSpec tree for the decode cache (``LM.init_cache``
    structure): batch over the FSDP axes, head/state dims over TP, with
    per-dim divisibility fallback (e.g. 8 KV heads on a 16-way axis
    replicate)."""
    del shape  # layout depends only on leaf shapes; kept for API parity
    F = fsdp_axes(mesh) or None
    tp = tp_axis(mesh)
    resolve = {"F": F, "tp": tp, None: None}

    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else None
        roles = tuple(resolve[r] for r in _CACHE_ROLES.get(name, ()))
        return _spec(mesh, tuple(leaf.shape), roles)

    return jax.tree_util.tree_map_with_path(rule, cache)


# ---------------------------------------------------------------------------
# batch specs + lifting
# ---------------------------------------------------------------------------

def batch_spec(mesh, cfg, batch: int, n_rest: int = 0) -> P:
    """Input-batch layout: dim 0 over the FSDP axes (when divisible),
    ``n_rest`` trailing dims replicated."""
    del cfg
    return _spec(mesh, (batch,) + (1,) * n_rest,
                 (fsdp_axes(mesh) or None,) + (None,) * n_rest)


def named(mesh, specs):
    """Lift a PartitionSpec tree into NamedShardings on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation-sharding rules
# ---------------------------------------------------------------------------

_ACT = threading.local()


def current_rules():
    """The (mesh, mode) pair of the innermost active
    :func:`activation_rules` context, or None."""
    return getattr(_ACT, "rules", None)


@contextmanager
def activation_rules(mesh, mode: str = "dp"):
    """Enable activation-sharding constraints for traces inside the
    context.  ``mode``: ``"dp"`` (batch over FSDP, TP on head/ff/vocab
    dims) or ``"sp"`` (additionally sequence-parallel residuals)."""
    prev = current_rules()
    _ACT.rules = (mesh, mode)
    try:
        yield
    finally:
        _ACT.rules = prev


def activation_spec(mesh, layout: str, shape: tuple,
                    mode: str = "dp") -> Optional[P]:
    """PartitionSpec for an activation of the given layout string, or
    None for an unknown layout / rank mismatch."""
    F = fsdp_axes(mesh) or None
    tp = tp_axis(mesh)
    roles = {
        "btd": (F, tp if mode == "sp" else None, None),
        "bthd": (F, None, tp, None),
        "btf": (F, None, tp),
        "btv": (F, None, tp),
    }.get(layout)
    if roles is None or len(roles) != len(shape):
        return None
    return _spec(mesh, shape, roles)


def constrain(x, layout: str):
    """Activation-sharding annotation.  Identity outside
    :func:`activation_rules`; inside, applies the mode's layout rule via
    ``with_sharding_constraint`` (divisibility-checked per dim)."""
    rules = current_rules()
    if rules is None:
        return x
    mesh, mode = rules
    spec = activation_spec(mesh, layout, tuple(x.shape), mode)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

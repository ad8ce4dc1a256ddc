"""Sharding rules: 2D (FSDP x TP) weight sharding + batch/cache specs.

PartitionSpec policy for the LM-workload side of the repo (models/,
train/, launch/dryrun); the clustering pipeline's sharding lives in
distributed/fit.py (training) and serve/extend.py::ShardedExtender
(the mesh-sharded extension matmul, ROADMAP "Serve subsystem").

Scheme:
- every 2D projection W (d_in, d_out): P(fsdp, tp) — input dim sharded over
  the data(+pod) axes ZeRO-3 style, output dim tensor-parallel over 'model';
  "reduction" projections that map back to the residual stream (wo, w2, cv,
  w_out, wb) use P(tp, fsdp) so the contraction dim is the TP-sharded one.
- embeddings: vocab over 'model' (padded to /128/tp), d_model over fsdp.
- MoE expert weights (E, d, f): experts replicated, d over fsdp, f over tp
  (divisibility-safe for E=8/16 vs the 16-way model axis).
- KV caches: batch over dp when divisible, sequence dim over 'model'
  (flash-decode style distributed softmax is then GSPMD-derived).
- every rule is guarded by divisibility; a non-divisible dim stays
  replicated rather than failing to lower.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# Names whose 2D matrices contract their TP-sharded input back to the
# residual stream: shard as P(tp, fsdp) instead of P(fsdp, tp).
_REDUCE_BACK = {"wo", "w2", "cv", "w_out", "wb"}
# Stacked containers: arrays carry a leading layer/superblock dim.
_STACKED = {"layers", "supers", "enc_layers", "dec_layers"}


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


class _ShardCtx(threading.local):
    def __init__(self):
        self.dp: Tuple[str, ...] = ()
        self.active = False
        self.seq_axis: Optional[str] = None    # sequence parallelism
        self.seq_div: int = 1                  # size of seq_axis
        self.tp: Optional[str] = None          # model axis name


_CTX = _ShardCtx()


@contextlib.contextmanager
def activation_sharding(dp: Tuple[str, ...], seq_axis: Optional[str] = None,
                        seq_div: int = 1, tp: Optional[str] = "model"):
    """Enable with_sharding_constraint on activations inside model code.

    seq_axis: also shard the sequence dim over this axis at layer
    boundaries (sequence parallelism — the TP all-reduce of layer outputs
    becomes reduce-scatter + all-gather, halving collective bytes)."""
    prev = (_CTX.dp, _CTX.active, _CTX.seq_axis, _CTX.seq_div, _CTX.tp)
    _CTX.dp, _CTX.active = tuple(dp), True
    _CTX.seq_axis, _CTX.seq_div = seq_axis, seq_div
    _CTX.tp = tp
    try:
        yield
    finally:
        (_CTX.dp, _CTX.active, _CTX.seq_axis, _CTX.seq_div,
         _CTX.tp) = prev


def maybe_shard(x: jnp.ndarray, kind: str = "btd") -> jnp.ndarray:
    """Constrain activation sharding if a context is active (no-op in tests).

    kind: 'btd' (B,S,d) batch-sharded; 'bd' (B,d).
    """
    if not _CTX.active:
        return x
    if kind == "btd":
        seq = (_CTX.seq_axis if _CTX.seq_axis and
               x.shape[1] % max(_CTX.seq_div, 1) == 0 else None)
        spec = P(_CTX.dp, seq, None)
    elif kind == "bd":
        spec = P(_CTX.dp, None)
    # MoE expert-pipeline pins (apply_moe): groups over dp, expert-ffn dim
    # over tp, everything else replicated — keeps routing gathers local and
    # forbids XLA from replicating the group dim (which otherwise shows up
    # as activation-sized data-axis all-reduces in the backward).
    elif kind == "moe_gtd":      # (G, Tg, d)
        spec = P(_CTX.dp if x.shape[0] % max(_dp_size(), 1) == 0 else None,
                 None, None)
    elif kind == "moe_gecd":     # (G, E, C, d)
        spec = P(_CTX.dp if x.shape[0] % max(_dp_size(), 1) == 0 else None,
                 None, None, None)
    elif kind == "moe_gecf":     # (G, E, C, f)
        spec = P(_CTX.dp if x.shape[0] % max(_dp_size(), 1) == 0 else None,
                 None, None, _CTX.tp)
    else:
        raise ValueError(kind)
    return jax.lax.with_sharding_constraint(x, spec)


def _dp_size() -> int:
    try:
        from jax.sharding import get_abstract_mesh
        m = get_abstract_mesh()
        if m is not None and m.axis_names:
            sizes = dict(m.shape)
            return _prod(sizes.get(a, 1) for a in _CTX.dp) or 1
    except Exception:
        pass
    return 1


def _axes_if_div(dim: int, axes, sizes: Dict[str, int]):
    """Return `axes` (str or tuple) if dim divides by their product."""
    if axes is None:
        return None
    tup = (axes,) if isinstance(axes, str) else tuple(axes)
    if not tup:
        return None
    if dim % _prod(sizes[a] for a in tup) == 0:
        return axes if isinstance(axes, str) else tup
    return None


def _param_rule(name: str, shape: Tuple[int, ...], stacked: bool,
                fsdp, tp, sizes: Dict[str, int]) -> P:
    lead = (None,) if stacked else ()
    core = shape[1:] if stacked else shape
    nd = len(core)
    if nd <= 1:
        return P(*lead, *(None,) * nd)
    if name == "embed":                     # (V, d)
        return P(*lead, _axes_if_div(core[0], tp, sizes),
                 _axes_if_div(core[1], fsdp, sizes))
    if name == "unembed":                   # (d, V)
        return P(*lead, _axes_if_div(core[0], fsdp, sizes),
                 _axes_if_div(core[1], tp, sizes))
    if name == "router":                    # (d, E)
        return P(*lead, _axes_if_div(core[0], fsdp, sizes), None)
    if nd == 3:                             # MoE expert weights (E, x, y)
        if name in _REDUCE_BACK:            # (E, f, d)
            return P(*lead, None, _axes_if_div(core[1], tp, sizes),
                     _axes_if_div(core[2], fsdp, sizes))
        return P(*lead, None, _axes_if_div(core[1], fsdp, sizes),
                 _axes_if_div(core[2], tp, sizes))
    if nd == 2:
        if name == "conv_w":                # (4, dr)
            return P(*lead, None, _axes_if_div(core[1], tp, sizes))
        if name in _REDUCE_BACK:
            return P(*lead, _axes_if_div(core[0], tp, sizes),
                     _axes_if_div(core[1], fsdp, sizes))
        return P(*lead, _axes_if_div(core[0], fsdp, sizes),
                 _axes_if_div(core[1], tp, sizes))
    return P(*lead, *(None,) * nd)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for part in path:
        if hasattr(part, "key"):
            names.append(str(part.key))
        elif hasattr(part, "idx"):
            names.append(str(part.idx))
        elif hasattr(part, "name"):
            names.append(str(part.name))
    return tuple(names)


def param_pspecs(params_shape, mesh, use_fsdp: bool = True) -> Any:
    """PartitionSpec pytree for a model param tree (works on eval_shape
    output — ShapeDtypeStructs — or concrete arrays).

    use_fsdp=False drops the data-axis factor (TP-only): used as the
    pre-gather target spec when cfg.pregather is on."""
    from repro.launch.mesh import dp_axes, tp_axis, mesh_axis_sizes
    fsdp = dp_axes(mesh) if use_fsdp else ()
    tp = tp_axis(mesh)
    sizes = mesh_axis_sizes(mesh)

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        stacked = any(n in _STACKED for n in names[:-1])
        return _param_rule(name, leaf.shape, stacked and len(leaf.shape) > 1,
                           fsdp, tp, sizes)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def state_pspecs(state_shape, mesh, zero1: bool = False) -> Any:
    """TrainState(params, opt{m,v,step}).

    Default (ZeRO-3-flavoured): params AND moments 2D-sharded (fsdp x tp).
    zero1=True: params/grads TP-only — every contraction is device-local
    (no data-axis partial-sum all-reduces of activation-sized tensors) —
    while the f32 moments stay fully 2D-sharded; the optimizer update
    reduce-scatters grads and all-gathers fresh params ONCE per step.
    """
    from repro.train.steps import TrainState
    params_spec = param_pspecs(state_shape.params, mesh,
                               use_fsdp=not zero1)
    return TrainState(
        params=params_spec,
        opt={"m": param_pspecs(state_shape.opt["m"], mesh),
             "v": param_pspecs(state_shape.opt["v"], mesh),
             "step": P()})


def batch_pspecs(batch_shape, mesh) -> Any:
    from repro.launch.mesh import dp_axes, mesh_axis_sizes
    dp = dp_axes(mesh)
    sizes = mesh_axis_sizes(mesh)

    def rule(path, leaf):
        b = _axes_if_div(leaf.shape[0], dp, sizes)
        return P(b, *(None,) * (len(leaf.shape) - 1))

    return jax.tree_util.tree_map_with_path(rule, batch_shape)


def cache_pspecs(cache_shape, mesh) -> Any:
    """KV caches: (L, B, T, H, hd) -> P(None, dp, tp-on-T, None, None);
    recurrent states: batch over dp, width over tp."""
    from repro.launch.mesh import dp_axes, tp_axis, mesh_axis_sizes
    dp = dp_axes(mesh)
    tp = tp_axis(mesh)
    sizes = mesh_axis_sizes(mesh)

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        s = leaf.shape
        if name == "pos":
            return P()
        if name in ("k", "v", "xk", "xv"):       # (L,B,T,H,hd)
            return P(None, _axes_if_div(s[1], dp, sizes),
                     _axes_if_div(s[2], tp, sizes), None, None)
        if name == "s":                          # (L,B,H,dk,dv)
            return P(None, _axes_if_div(s[1], dp, sizes),
                     _axes_if_div(s[2], tp, sizes), None, None)
        if name in ("tm", "cm", "h"):            # (L,B,d)
            return P(None, _axes_if_div(s[1], dp, sizes),
                     _axes_if_div(s[2], tp, sizes))
        if name == "conv":                       # (L,B,3,d)
            return P(None, _axes_if_div(s[1], dp, sizes), None,
                     _axes_if_div(s[3], tp, sizes))
        return P(*(None,) * len(s))

    return jax.tree_util.tree_map_with_path(rule, cache_shape)

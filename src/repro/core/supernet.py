"""Weight-sharing super-network: parameter views for the client/server split.

The super-network is the stacked-layer parameter tree from
``repro.models.model.init_params``. A client subnetwork of depth ``d`` is a
*contiguous prefix* of the split stack (paper §II-A); here that is a slice of
the leading ``L`` axis plus the input-side parameters (embedding / patch /
frame projections), which every client holds (they are "layer 0" of the
prefix in the paper's sense).

Beside depth, the supernet slices *width* (paper §II-A, Fig. 2): a width
tier ``w in (0, 1]`` keeps the leading-channel prefix of every layer's MLP
hidden dim and attention heads (whole GQA groups, so kept query heads never
read a pruned KV head). ``width_cfg`` derives the sliced ``ModelConfig``
(hashable — it doubles as the jit static key), ``width_plan`` names the
sliced (axis, keep) per leaf, and ``slice_width`` / ``mask_width`` /
``widen_width`` / ``scatter_width`` are the four views the slice-parity
contract in ``tests/test_supernet_width.py`` pins:

  slice  — take the kept prefix (the client's download);
  mask   — zero the pruned coordinates in a full tree (slice-then-forward
           == forward-then-mask, because pruned head/hidden outputs are
           killed by the zeroed ``wo`` / ``w_down`` rows);
  widen  — zero-embed a sliced tree back to full shape
           (``widen(slice(t)) == mask(t)`` identically);
  scatter— write a sliced tree into a full one, touching ONLY the kept
           coordinates (gradient scatter-back into the shared supernet).

The residual stream (``d_model``, the smashed data) stays full-width at
every tier, so the server branch and the fault-tolerant local head are
width-oblivious.

``split_params`` / ``merge_params`` give disjoint client | server | local
views so TPGF can compute per-branch gradients without masking tricks.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]

# input-side parameter names that always live on the client
_CLIENT_INPUT_KEYS = ("embed", "vision_proj", "patch_embed", "patch_bias",
                      "pos_embed", "frame_proj")
# the fault-tolerant classifier phi_i — never aggregated (paper §II-D)
_LOCAL_KEYS = ("local_head", "local_head_bias")


def split_stack_name(cfg: ModelConfig) -> str:
    return "enc_layers" if cfg.is_encdec else "layers"


def prefix(stack, d: int):
    return jax.tree.map(lambda x: x[:d], stack)


def suffix(stack, d: int):
    return jax.tree.map(lambda x: x[d:], stack)


# --------------------------------------------------------------- width views

def width_cfg(cfg: ModelConfig, width: float) -> ModelConfig:
    """The sliced ``ModelConfig`` for a width tier ``w in (0, 1]``.

    Heads slice by whole GQA groups — ``Kw = max(1, round(w * n_kv_heads))``
    KV heads, ``Hw = (n_heads // n_kv_heads) * Kw`` query heads — so a kept
    query head always reads a kept KV head. ``head_dim`` is pinned
    explicitly (``resolved_head_dim`` would recompute it from the sliced
    ``n_heads``). The returned config is frozen/hashable, so it serves both
    as the apply-time dimension source and as part of the kernel's jit
    static key.
    """
    if width >= 1.0:
        return cfg
    hd = cfg.resolved_head_dim
    group = max(1, cfg.n_heads // max(1, cfg.n_kv_heads))
    kv = max(1, int(round(width * cfg.n_kv_heads)))
    dff = max(1, int(round(width * cfg.d_ff)))
    return cfg.replace(n_heads=group * kv, n_kv_heads=kv, d_ff=dff,
                       head_dim=hd)


def width_plan(cfg: ModelConfig, width: float) -> Dict[str, Tuple[int, int]]:
    """leaf-name -> (axis, keep): the sliced axis and kept prefix length.

    Axes are negative so one plan covers ``[...]``, ``[L, ...]`` and
    ``[N, L, ...]`` leaves (and MoE ``[E, dm, dff]`` expert weights). Names
    absent from the plan — norms, ``b_down``, branch scales, SSM/router,
    input-side and head parameters — stay full-width: they live on the
    ``d_model`` residual stream, which never slices.
    """
    wcfg = width_cfg(cfg, width)
    hd = cfg.resolved_head_dim
    qh = wcfg.n_heads * hd
    kvh = wcfg.n_kv_heads * hd
    dff = wcfg.d_ff
    return {
        "wq": (-1, qh), "bq": (-1, qh),
        "wk": (-1, kvh), "wv": (-1, kvh), "bk": (-1, kvh), "bv": (-1, kvh),
        "wo": (-2, qh),
        "w_gate": (-1, dff), "w_up": (-1, dff), "b_up": (-1, dff),
        "w_down": (-2, dff),
    }


def _leaf_name(path) -> Any:
    k = path[-1]
    return getattr(k, "key", getattr(k, "idx", None))


def _map_width(cfg: ModelConfig, tree, width: float, fn):
    """Apply ``fn(leaf, axis, keep)`` to every plan leaf, identity elsewhere."""
    plan = width_plan(cfg, width)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [fn(leaf, *plan[_leaf_name(path)])
           if _leaf_name(path) in plan else leaf
           for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def slice_width(cfg: ModelConfig, tree, width: float):
    """Kept-prefix view of a (full-width) parameter/gradient tree."""
    if width >= 1.0:
        return tree

    def take(x, ax, keep):
        return jax.lax.slice_in_dim(x, 0, keep, axis=x.ndim + ax)

    return _map_width(cfg, tree, width, take)


def mask_width(cfg: ModelConfig, tree, width: float):
    """Zero the pruned coordinates of a full-width tree (NaN-safe where)."""
    if width >= 1.0:
        return tree

    def mask(x, ax, keep):
        axis = x.ndim + ax
        kept = jnp.arange(x.shape[axis]) < keep
        kept = kept.reshape((-1,) + (1,) * (x.ndim - 1 - axis))
        return jnp.where(kept, x, jnp.zeros((), x.dtype))

    return _map_width(cfg, tree, width, mask)


def widen_width(cfg: ModelConfig, tree, width: float, *, full_cfg=None):
    """Zero-embed a sliced tree back to full width (the scatter identity
    ``widen(slice(t)) == mask(t)``). ``full_cfg`` defaults to ``cfg``."""
    if width >= 1.0:
        return tree
    full = width_plan(full_cfg or cfg, 1.0)
    plan = width_plan(full_cfg or cfg, width)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = _leaf_name(path)
        if name in plan:
            ax, keep = plan[name]
            axis = leaf.ndim + ax
            pad = [(0, 0)] * leaf.ndim
            pad[axis] = (0, full[name][1] - keep)
            leaf = jnp.pad(leaf, pad)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def scatter_width(cfg: ModelConfig, full_tree, sliced_tree, width: float):
    """Write a sliced tree into a full-width one, touching ONLY the kept
    coordinates (plan leaves: kept prefix; non-plan leaves are fully held
    by the client, so they are replaced whole)."""
    if width >= 1.0:
        return sliced_tree
    plan = width_plan(cfg, width)
    flat_f, treedef = jax.tree_util.tree_flatten_with_path(full_tree)
    flat_s = jax.tree_util.tree_flatten_with_path(sliced_tree)[0]
    out = []
    for (path, f), (_, s) in zip(flat_f, flat_s):
        name = _leaf_name(path)
        if name in plan:
            ax, keep = plan[name]
            axis = f.ndim + ax
            idx = tuple(slice(0, keep) if i == axis else slice(None)
                        for i in range(f.ndim))
            out.append(f.at[idx].set(s.astype(f.dtype)))
        else:
            out.append(s.astype(f.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def width_keep_sizes(cfg: ModelConfig, width: float) -> Dict[str, int]:
    """leaf-name -> kept prefix length (host-side helper for the
    per-coordinate aggregation denominators in ``core.aggregation``)."""
    return {k: keep for k, (_, keep) in width_plan(cfg, width).items()}


def split_params(cfg: ModelConfig, params: Params, d=None,
                 width: float = 1.0) -> Tuple[Params, Params, Params]:
    """-> (client theta_i, server theta_s, local phi_i), disjoint.

    A static (Python int) ``d`` slices the depth window at trace time:
    the client stack holds rows ``[:d]`` and the server stack rows
    ``[d:]``. ``d=None`` builds the *runtime-depth* views instead — BOTH
    stacks keep all ``L`` rows (width still slices the client's channel
    dims) and the kernels pass ``d`` as a jax scalar to the masked-scan
    apply functions, so one jit program serves every depth tier.

    ``width < 1`` width-slices the CLIENT stack only: the smashed data is
    full ``d_model``, so the server suffix and the local head stay
    full-width regardless of the client's tier.
    """
    sname = split_stack_name(cfg)
    client: Params = {}
    server: Params = {}
    local: Params = {}
    for k, v in params.items():
        if k in _LOCAL_KEYS:
            local[k] = v
        elif k == sname:
            cstack = v if d is None else prefix(v, d)
            if width < 1.0:
                cstack = slice_width(cfg, cstack, width)
            client[k] = cstack
            server[k] = v if d is None else suffix(v, d)
        elif k in _CLIENT_INPUT_KEYS and not (cfg.is_encdec and k == "embed"):
            # NB: the enc-dec decoder embedding is server-side (the split
            # stack is the encoder), so whisper's "embed" stays on the server
            client[k] = v
        else:
            server[k] = v
    return client, server, local


def merge_params(cfg: ModelConfig, client: Params, server: Params,
                 local: Params, d=None) -> Params:
    """Inverse of ``split_params``. With the static views (``d=None``
    here), the two depth slices concatenate back. With full-``L``
    runtime views, pass the jax scalar ``d`` and each stack row selects
    client (``row < d``) or server (``row >= d``) — the same rows the
    masked scans actually trained."""
    sname = split_stack_name(cfg)
    out: Params = {}
    for k, v in client.items():
        if k == sname:
            if d is None:
                out[k] = jax.tree.map(
                    lambda a, b: jax.numpy.concatenate([a, b], axis=0),
                    v, server[k])
            else:
                out[k] = jax.tree.map(
                    lambda a, b: depth_select(a, b, d, keep="prefix"),
                    v, server[k])
        else:
            out[k] = v
    for k, v in server.items():
        if k not in out:
            out[k] = v
    out.update(local)
    return out


def depth_select(new, old, d, *, keep: str, axis: int = 0):
    """Row-select along a stacked-layer axis: rows ``< d`` come from
    ``new`` when ``keep="prefix"`` (else from ``old``), and vice versa
    for the suffix. The kernels use this to freeze the out-of-window rows
    of full-``L`` runtime-depth stacks — reverting an optimizer update on
    a frozen row to its carried value is bit-equal to never updating it,
    because every fleet optimizer is elementwise."""
    rows = jnp.arange(new.shape[axis]).reshape(
        (1,) * axis + (-1,) + (1,) * (new.ndim - 1 - axis))
    in_prefix = rows < d
    take_new = in_prefix if keep == "prefix" else ~in_prefix
    return jnp.where(take_new, new, old)


def depth_freeze(cfg: ModelConfig, new, old, d, *, keep: str,
                 axis: int = 0):
    """Revert the out-of-depth-window rows of the split STACK inside a
    params-shaped tree (client/server view) or an optimizer-state dict.

    Only the ``split_stack_name`` subtree is row-selected (via
    :func:`depth_select`); non-stack leaves — input-side parameters,
    heads, the enc-dec decoder, optimizer bookkeeping like AdamW's ``t``
    — pass through from ``new`` untouched. For an optimizer state, every
    moment entry (a dict mirroring the params tree) gets the same
    treatment; stateless ``()`` states pass through whole.
    """
    sname = split_stack_name(cfg)

    def fz(ntree, otree):
        out = dict(ntree)
        out[sname] = jax.tree.map(
            lambda a, b: depth_select(a, b, d, keep=keep, axis=axis),
            ntree[sname], otree[sname])
        return out

    if isinstance(new, dict) and sname in new:
        return fz(new, old)
    if isinstance(new, dict):   # optimizer state: moment entries only
        return {k: fz(v, old[k])
                if isinstance(v, dict) and sname in v else v
                for k, v in new.items()}
    return new


@functools.lru_cache(maxsize=None)
def _split_sizes(cfg: ModelConfig, treedef, avals, d,
                 width: float) -> Tuple[int, int, int]:
    """(client count, server count, client + local bytes) of one split,
    traced from shapes alone: ``split_params`` under ``jax.eval_shape``."""
    tree = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, dt) for s, dt in avals])
    client, server, local = jax.eval_shape(
        lambda p: split_params(cfg, p, d, width), tree)
    count = lambda t: sum(math.prod(x.shape) for x in jax.tree.leaves(t))
    nbytes = sum(math.prod(x.shape) * x.dtype.itemsize
                 for x in jax.tree.leaves((client, local)))
    return count(client), count(server), nbytes


def split_sizes(cfg: ModelConfig, params: Params, d,
                width: float = 1.0) -> Tuple[int, int, int]:
    """(client parameter count, server parameter count, client + local
    bytes) of the depth-``d`` width-``w`` split.

    Computed from the parameters' tree structure, shapes and dtypes only
    — ``params`` may be device arrays or ``jax.ShapeDtypeStruct`` leaves
    — and cached by (cfg, that signature, d, width), so a fleet's
    accounting traces each split once and later rounds, whose parameters
    are new arrays of the same shapes, read the cache. No device program
    runs."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    avals = tuple((tuple(x.shape), jnp.dtype(x.dtype)) for x in leaves)
    return _split_sizes(cfg, treedef, avals,
                        None if d is None else int(d), float(width))


def size_cache_misses() -> int:
    """Splits sized so far (cache misses of :func:`split_sizes`); a delta
    around a round counts the accounting work that round did."""
    return _split_sizes.cache_info().misses


def client_param_bytes(cfg: ModelConfig, params: Params, d: int,
                       width: float = 1.0) -> int:
    """Size of a (depth, width) subnetwork — the per-round download cost
    (client view plus local head; :func:`split_sizes`)."""
    return split_sizes(cfg, params, d, width)[2]


def smashed_bytes(z) -> int:
    return int(z.size) * z.dtype.itemsize

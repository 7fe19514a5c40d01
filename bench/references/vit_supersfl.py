"""Plain reference: SuperSFL rounds (Alg. 2/3, Eq. 3/4/6/8) over a ViT.

Written from the method's description in straightforward ``jax.numpy``,
layer by layer and client by client, with nothing from the program under
test. One round, for each depth cohort in ascending depth:

* the cohort splits into width groups (ascending width); every group starts
  from the round-start server branch;
* each of ``local_steps`` steps, every client of the group runs TPGF at its
  depth ``d`` and width ``w``: the client prefix (input projection and
  layers ``< d``, width-sliced) makes the smashed activations ``z``; the
  local head's loss ``Lc`` and the server suffix's loss ``Ls`` (layers
  ``>= d`` at full width, then the global head) each back-propagate
  through the prefix; the local branch's prefix gradient is clipped to
  global L2 norm ``tpgf_clip``; the prefix takes the Eq. 4 blend
  ``wc * g_local + (1 - wc) * g_server`` with Eq. 3's
  ``wc = d / L * (1 / Lc) / (1 / Lc + 1 / Ls)``. A client whose server is
  unavailable this round takes the clipped local gradient alone and gives
  the server nothing (Alg. 3);
* the server branch takes one SGD step per local step with the mean of
  the group's server gradients (unavailable clients count as zero in the
  mean); a group where no client reached the server leaves it unchanged;
* a cohort with several width groups fuses their server branches into one:
  ``base + sum_t m_t / sum(m) * (x_t - base)``, ``m_t`` the summed inverse
  fused losses of the group's available clients (``base`` if all are 0);
* the cohort's server result replaces rows ``>= d`` of the round's server
  view, and the global head.

Then Eq. 6 weights ``w_i = d_i / sum(d) * (1 / L_i) / sum(1 / L)`` over
the trained clients (``L_i`` the fused loss of its last step, or ``Lc``
when unavailable) and Eq. 8 layer-aligned averaging with server weight
``agg_lambda``: per stack row and per channel, only clients that hold that
row (``l < d_i``) and that channel (inside their width slice) count; the
input projection averages over all trained clients. The round's loss is
the mean of the ``L_i``.

A width-``w`` slice keeps the first ``max(1, round(w * H))`` heads and the
first ``max(1, round(w * d_ff))`` hidden units of every layer. The model
follows the system's ViT: 4x4 patch projection plus learned positions,
pre-norm encoder layers (layer norm eps 1e-5, tanh-approximated GELU), mean
pooling, a linear head, no final norm.

``dtype`` and ``precision`` choose the arithmetic: float32 at ``highest``
is the reference; bfloat16 at ``default`` is the lower-precision control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.vit import kept
from bench.inputs import batch_indices

PLAN = {("attn", "wq"): -1, ("attn", "wk"): -1, ("attn", "wv"): -1,
        ("attn", "wo"): -2, ("mlp", "w_up"): -1, ("mlp", "b_up"): -1,
        ("mlp", "w_down"): -2}


def width_masks(model: dict, width: float, dtype):
    h, f = kept(model, width)
    hd = model["head_dim"]
    cols = (jnp.arange(model["n_heads"] * hd) < h * hd).astype(dtype)
    hidden = (jnp.arange(model["d_ff"]) < f).astype(dtype)
    return cols, hidden


# ------------------------------------------------------------ model pieces

def _layernorm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + 1e-5)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(n_heads, head_dim, p, h, cols, hidden):
    """One pre-norm encoder layer; ``cols``/``hidden`` zero the pruned
    heads and hidden units of a width slice."""
    B, T, D = h.shape
    a = p["attn"]
    x = _layernorm(h, p["attn_norm_scale"], p["attn_norm_bias"])
    q = (x @ (a["wq"] * cols)).reshape(B, T, n_heads, head_dim)
    k = (x @ (a["wk"] * cols)).reshape(B, T, n_heads, head_dim)
    v = (x @ (a["wv"] * cols)).reshape(B, T, n_heads, head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
    s = s.astype(jnp.float32)
    prob = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    prob = (prob / jnp.sum(prob, -1, keepdims=True)).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob, v).reshape(B, T, -1)
    h = h + o @ (a["wo"] * cols[:, None])
    m = p["mlp"]
    x = _layernorm(h, p["mlp_norm_scale"], p["mlp_norm_bias"])
    u = _gelu(x @ (m["w_up"] * hidden) + m["b_up"] * hidden)
    return h + u @ (m["w_down"] * hidden[:, None]) + m["b_down"]


def _embed(patch, img, emb):
    B, S, _, C = img.shape
    g = S // patch
    x = img.reshape(B, g, patch, g, patch, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, g * g, patch * patch * C).astype(emb["patch_embed"].dtype)
    return x @ emb["patch_embed"] + emb["patch_bias"] + emb["pos_embed"][None]


def _xent(logits, labels):
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, -1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), -1)) + m[:, 0]
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(logz - gold)


def _head_loss(w, b, h, labels):
    return _xent(jnp.mean(h, axis=1) @ w + b, labels)


class Model:
    """Jitted per-layer pieces for one (model, dtype, precision)."""

    def __init__(self, model: dict, dtype, precision: str):
        self.m = model
        self.dtype = jnp.dtype(dtype)
        self.precision = precision
        H, hd = model["n_heads"], model["head_dim"]
        layer = functools.partial(_layer, H, hd)
        patch = model["patch_size"]
        embed = functools.partial(_embed, patch)
        self.layer = jax.jit(layer)
        self.layer_vjp = jax.jit(
            lambda p, h, c, f, g: jax.vjp(
                lambda p_, h_: layer(p_, h_, c, f), p, h)[1](g))
        self.embed = jax.jit(embed)
        self.embed_vjp = jax.jit(
            lambda e, img, g: jax.vjp(lambda e_: embed(img, e_), e)[1](g)[0])
        self.head = jax.jit(jax.value_and_grad(_head_loss, argnums=(0, 1, 2)))
        self.sgd = jax.jit(lambda p, g, lr: jax.tree.map(
            lambda a, b: (a - lr * b).astype(a.dtype), p, g))
        self.clip = jax.jit(functools.partial(_clip, model["tpgf_clip"]))
        self.blend = jax.jit(functools.partial(
            _blend, model["n_layers"], model["tpgf_eps"]))
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def ctx(self):
        return jax.default_matmul_precision(self.precision)


# ----------------------------------------------------------------- TPGF

def _clip(tau, tree):
    """Scale ``tree`` to global L2 norm at most ``tau``."""
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    s = jnp.minimum(1.0, tau / (jnp.sqrt(sq) + 1e-12))
    return jax.tree.map(lambda x: (x * s).astype(x.dtype), tree)


def _blend(L, eps, g_local, g_server, lc, ls, d):
    """Eq. 3/4: ``wc * g_local + (1 - wc) * g_server``."""
    ic, is_ = 1.0 / (lc + eps), 1.0 / (ls + eps)
    wc = d / L * (ic / (ic + is_))
    return jax.tree.map(
        lambda x, y: (wc * x.astype(jnp.float32)
                      + (1.0 - wc) * y.astype(jnp.float32)).astype(x.dtype),
        g_local, g_server)


def tpgf(M: Model, client, server, lhead, img, labels, d, masks, avail):
    """One client's TPGF gradients at depth ``d``.

    client: {"embed": {...}, "layers": [d layer trees]} (full-width arrays,
    pruned channels zero); server: {"layers": [L - d trees], "head",
    "head_bias"}; lhead: (w, b). Returns (g_client, g_server or None,
    g_lhead, Lc, Ls or None), the losses as device scalars."""
    m = M.m
    L = m["n_layers"]
    cols, hidden = masks
    full = (jnp.ones_like(cols), jnp.ones_like(hidden))
    hs = [M.embed(img, client["embed"])]
    for p in client["layers"]:
        hs.append(M.layer(p, hs[-1], cols, hidden))
    z = hs[-1]
    lc, (gw, gb, gz_local) = M.head(lhead[0], lhead[1], z, labels)

    def back(gz):
        g_layers = [None] * d
        g = gz
        for i in range(d - 1, -1, -1):
            g_layers[i], g = M.layer_vjp(client["layers"][i], hs[i], cols,
                                         hidden, g)
        return {"embed": M.embed_vjp(client["embed"], img, g),
                "layers": g_layers}

    g_local = M.clip(back(gz_local))
    if not avail:
        return g_local, None, (gw, gb), lc, None
    ss = [z]
    for p in server["layers"]:
        ss.append(M.layer(p, ss[-1], *full))
    ls, (ghw, ghb, g) = M.head(server["head"], server["head_bias"], ss[-1],
                               labels)
    g_srv = [None] * (L - d)
    for i in range(L - d - 1, -1, -1):
        g_srv[i], g = M.layer_vjp(server["layers"][i], ss[i], *full, g)
    g_client = M.blend(g_local, back(g), lc, ls, jnp.float32(d))
    return (g_client, {"layers": g_srv, "head": ghw, "head_bias": ghb},
            (gw, gb), lc, ls)


def fused_loss(model, lc, ls, d):
    if ls is None:
        return lc
    eps = model["tpgf_eps"]
    ic, is_ = 1.0 / (lc + eps), 1.0 / (ls + eps)
    wc = d / model["n_layers"] * (ic / (ic + is_))
    return wc * lc + (1.0 - wc) * ls


# ------------------------------------------------------------------ round

def _row(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def to_layers(params, L):
    """Stacked engine layout -> per-layer lists (the reference's layout)."""
    return {"embed": {k: params[k] for k in
                      ("patch_embed", "patch_bias", "pos_embed")},
            "layers": [_row(params["layers"], i) for i in range(L)],
            "head": params["head"], "head_bias": params["head_bias"],
            "local_head": params["local_head"],
            "local_head_bias": params["local_head_bias"]}


def leaf_norms(state, heads0=None):
    """name -> L2 norm of every leaf of (global params, local heads), with
    the names of the engine's trees (stack leaves over all rows)."""
    out = {}
    g = state["global"]
    for k in ("patch_embed", "patch_bias", "pos_embed", "head", "head_bias",
              "local_head", "local_head_bias"):
        x = g["embed"][k] if k in g["embed"] else g[k]
        out[f"params/{k}"] = float(jnp.linalg.norm(
            x.astype(jnp.float32).ravel()))
    flat = jax.tree_util.tree_flatten_with_path(g["layers"][0])[0]
    for path, _ in flat:
        name = "/".join(str(getattr(q, "key", q)) for q in path)
        sq = 0.0
        for lay in g["layers"]:
            x = lay
            for q in path:
                x = x[q.key]
            sq += float(jnp.sum(jnp.square(x.astype(jnp.float32))))
        out[f"params/layers/{name}"] = math.sqrt(sq)
    for k in ("local_head", "local_head_bias"):
        out[f"local_heads/{k}"] = float(jnp.linalg.norm(
            state["heads"][k].astype(jnp.float32).ravel()))
    return out


def diff_state(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


def run(model: dict, traffic: dict, lr: float, params, heads, data, fleet,
        avail, batch_rng, rounds: int, *, dtype="float32",
        precision="highest", on_round=None):
    """``rounds`` SuperSFL rounds from (params, heads) in the engine layout.

    data: ``inputs.Dataset``; fleet: ``inputs.Fleet``; avail: [rounds, N]
    bool; batch_rng: numpy Generator of the batch stream. Calls
    ``on_round(r, loss, state)`` after each round, ``state`` being
    ``{"global": per-layer tree, "heads": {local_head, local_head_bias}}``.
    """
    M = Model(model, dtype, precision)
    dt = M.dtype
    L = model["n_layers"]
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x).astype(dt), t)
    with M.ctx():
        g = cast(to_layers(params, L))
        hs = cast(heads)
        images = jnp.asarray(data.images).astype(dt)
        labels = jnp.asarray(data.labels)
        lr_ = jnp.asarray(lr, dt)
        for r in range(rounds):
            g, hs, loss = _round(M, model, traffic, lr_, g, hs, images,
                                 labels, data, fleet, avail[r], batch_rng)
            if on_round is not None:
                on_round(r + 1, loss, {"global": g, "heads": hs})
    return g, hs


def _round(M, model, traffic, lr, g, heads, images, labels, data, fleet,
           avail, batch_rng):
    steps, B = traffic["local_steps"], traffic["batch_size"]
    srv0 = {"layers": g["layers"], "head": g["head"],
            "head_bias": g["head_bias"]}
    view = dict(srv0, layers=list(srv0["layers"]))
    trained = {}                       # id -> (client tree, loss)
    new_heads = {i: (heads["local_head"][i], heads["local_head_bias"][i])
                 for i in range(len(fleet.depths))}
    for d, groups in fleet.cohorts():
        results = []
        for w, ids in groups:
            masks = width_masks(model, w, M.dtype)
            cols, hidden = masks
            idx = batch_indices(batch_rng, data, ids, steps, B)
            base_client = {"embed": g["embed"], "layers": [
                _mask_layer(g["layers"][i], cols, hidden) for i in range(d)]}
            clients = {int(i): base_client for i in ids}
            srv = {"layers": srv0["layers"][d:], "head": srv0["head"],
                   "head_bias": srv0["head_bias"]}
            last = {}
            any_av = bool(avail[ids].any())
            for s in range(steps):
                gsum, n = None, 0
                for j, i in enumerate(ids):
                    i = int(i)
                    bi = jnp.asarray(idx[s, j])
                    gc, gs, gl, lc, ls = tpgf(
                        M, clients[i], srv, new_heads[i], images[bi],
                        labels[bi], d, masks, bool(avail[i]))
                    clients[i] = M.sgd(clients[i], gc, lr)
                    new_heads[i] = M.sgd(new_heads[i], gl, lr)
                    n += 1
                    if gs is not None:
                        gsum = gs if gsum is None else M.add(gsum, gs)
                    last[i] = (lc, ls)
                if any_av:
                    gmean = jax.tree.map(lambda x: x / n, gsum)
                    srv = M.sgd(srv, gmean, lr)
            last = jax.device_get(last)
            mass = 0.0
            for i in ids:
                i = int(i)
                lc, ls = (None if x is None else float(x) for x in last[i])
                lf = fused_loss(model, lc, ls, d)
                trained[i] = (clients[i], lf, w)
                if ls is not None:
                    mass += 1.0 / (lf + model["tpgf_eps"])
            results.append((mass, srv))
        if len(results) == 1:
            srv = results[0][1]
        else:
            base = {"layers": srv0["layers"][d:], "head": srv0["head"],
                    "head_bias": srv0["head_bias"]}
            tot = sum(m for m, _ in results)
            if tot > 0:
                acc = None
                for m_, x in results:
                    term = jax.tree.map(
                        lambda a, b: (m_ / tot) * (a.astype(jnp.float32)
                                                   - b.astype(jnp.float32)),
                        x, base)
                    acc = term if acc is None else jax.tree.map(jnp.add, acc,
                                                                term)
                srv = jax.tree.map(lambda b, a: (b.astype(jnp.float32) + a)
                                   .astype(b.dtype), base, acc)
            else:
                srv = base
        view["layers"][d:] = srv["layers"]
        view["head"], view["head_bias"] = srv["head"], srv["head_bias"]
    g, loss = _aggregate(M, model, g, view, trained, fleet)
    hs = {"local_head": jnp.stack([new_heads[i][0]
                                   for i in range(len(fleet.depths))]),
          "local_head_bias": jnp.stack([new_heads[i][1]
                                        for i in range(len(fleet.depths))])}
    return g, hs, loss


def _mask_layer(p, cols, hidden):
    out = jax.tree.map(lambda x: x, p)
    a, m = dict(p["attn"]), dict(p["mlp"])
    for k in ("wq", "wk", "wv"):
        a[k] = a[k] * cols
    a["wo"] = a["wo"] * cols[:, None]
    m["w_up"] = m["w_up"] * hidden
    m["b_up"] = m["b_up"] * hidden
    m["w_down"] = m["w_down"] * hidden[:, None]
    out["attn"], out["mlp"] = a, m
    return out


def _aggregate(M, model, g, view, trained, fleet):
    """Eq. 6 weights, Eq. 8 per-row, per-channel averaging."""
    L = model["n_layers"]
    lam = model["agg_lambda"]
    eps = model["tpgf_eps"]
    ids = sorted(trained)
    dep = np.array([fleet.depths[i] for i in ids], np.float64)
    inv = np.array([1.0 / (trained[i][1] + eps) for i in ids], np.float64)
    wts = (dep / dep.sum()) * (inv / inv.sum())
    loss = float(np.mean([trained[i][1] for i in ids]))
    f32 = lambda x: x.astype(jnp.float32)
    new = dict(g)
    emb = {}
    for k, s in g["embed"].items():
        num = sum(float(w) * f32(trained[i][0]["embed"][k])
                  for w, i in zip(wts, ids))
        emb[k] = ((num + lam * f32(s)) / (float(wts.sum()) + lam)).astype(
            s.dtype)
    new["embed"] = emb
    layers = []
    for li in range(L):
        s = view["layers"][li]
        holders = [(float(w), i) for w, i in zip(wts, ids)
                   if li < fleet.depths[i]]
        out = jax.tree.map(lambda x: x, s)
        for grp in ("attn", "mlp"):
            out[grp] = dict(s[grp])
        for path, sv in _leaves(s):
            num = f32(sv) * lam
            den = jnp.full(sv.shape, lam, jnp.float32)
            ax = PLAN.get(path)
            for w, i in holders:
                num = num + w * f32(_get(trained[i][0]["layers"][li], path))
                if ax is None:
                    den = den + w
                else:
                    h, f = kept(model, trained[i][2])
                    keep = (f if path[0] == "mlp"
                            else h * model["head_dim"])
                    n = sv.shape[ax]
                    shape = [1] * sv.ndim
                    shape[ax] = n
                    den = den + w * (jnp.arange(n) < keep).astype(
                        jnp.float32).reshape(shape)
            _set(out, path, (num / den).astype(sv.dtype))
        layers.append(out)
    new["layers"] = layers
    new["head"], new["head_bias"] = view["head"], view["head_bias"]
    return new, loss


def _leaves(tree, prefix=()):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = value

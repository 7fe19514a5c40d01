"""The ``vit`` family: weights, data and required work of a ViT classifier.

What ``spec.Cell.family`` asks of a family (``bench/spec.py``), for the
system's ViT: a patch projection plus learned positions, pre-norm encoder
layers, mean pooling, a linear head, on CIFAR-shaped images.

The work a SuperSFL round requires, from shapes alone. Counts what Alg. 2/3
needs, not what an implementation executes: matmul FLOPs (2 per
multiply-add) of real clients only — no padded bucket slots, no masked
inactive rows of a runtime-depth scan, no recomputation. Normalisations,
softmax and activations are not counted.

Per real client, per local step, per sample of ``T`` tokens:

* client prefix forward: the patch projection and layers ``< d`` at the
  client's width;
* the local head, forward and backward;
* one prefix backward per TPGF branch (the local branch's is needed on
  its own for its clip): each layer's weight and input gradients; the patch
  projection needs only its weight gradient;
* when the client reached the server that round: the server suffix, layers
  ``>= d`` at full width, and the global head, forward and backward, and
  the second prefix backward. An unavailable client needs one prefix
  backward.

A backward pass is two matmuls per forward matmul (weights and input),
both operands' gradients for the two attention matmuls.

Required bytes (for a roofline): per local step, every real client's
parameters at its (depth, width) and its local head read and written once,
the group's server branch read and written once, and the batch read once;
float32. SGD keeps no optimizer state.
"""
from __future__ import annotations

import math

# ------------------------------------------------------------------ weights


def weight_shapes(model: dict, n_clients: int):
    """(params, local_heads) as trees of (shape, init std or 'zeros'/'ones')."""
    D, L, C = model["d_model"], model["n_layers"], model["n_classes"]
    H, hd, F = model["n_heads"], model["head_dim"], model["d_ff"]
    p = model["patch_size"]
    T = (model["image_size"] // p) ** 2
    out_std = 0.02 / math.sqrt(2 * L)
    layers = {
        "attn_norm_scale": ((L, D), "ones"),
        "attn_norm_bias": ((L, D), "zeros"),
        "attn": {"wq": ((L, D, H * hd), 0.02), "wk": ((L, D, H * hd), 0.02),
                 "wv": ((L, D, H * hd), 0.02),
                 "wo": ((L, H * hd, D), out_std)},
        "mlp_norm_scale": ((L, D), "ones"),
        "mlp_norm_bias": ((L, D), "zeros"),
        "mlp": {"w_up": ((L, D, F), 0.02), "b_up": ((L, F), "zeros"),
                "w_down": ((L, F, D), out_std), "b_down": ((L, D), "zeros")},
    }
    params = {"patch_embed": ((p * p * 3, D), 0.02),
              "patch_bias": ((D,), "zeros"),
              "pos_embed": ((T, D), 0.02),
              "layers": layers,
              "head": ((D, C), 0.02), "head_bias": ((C,), "zeros"),
              "local_head": ((D, C), 0.02),
              "local_head_bias": ((C,), "zeros")}
    heads = {"local_head": ((n_clients, D, C), 0.02),
             "local_head_bias": ((n_clients, C), "zeros")}
    return params, heads


# --------------------------------------------------------------------- data


def make_samples(model: dict, traffic: dict, rng):
    """CIFAR-shaped synthetic images, [S, H, W, 3] float64, and their
    labels: a fixed prototype per class plus Gaussian noise — a copy of the
    program's own generator (``repro.data.synthetic``), kept here so a
    program change cannot move it."""
    C, S = model["n_classes"], model["image_size"]
    protos = rng.normal(0.0, 1.0, (C, S, S, 3))
    labels = rng.integers(0, C, traffic["samples"])
    images = protos[labels] + rng.normal(0.0, traffic["noise"],
                                         (traffic["samples"], S, S, 3))
    return images, labels


# -------------------------------------------------------------------- work


def kept(model: dict, width: float):
    """(heads, hidden units) a width tier keeps: the leading
    ``max(1, round(w * n))`` of each."""
    if width >= 1.0:
        return model["n_heads"], model["d_ff"]
    return (max(1, int(round(width * model["n_heads"]))),
            max(1, int(round(width * model["d_ff"]))))


def tokens(model: dict) -> int:
    return (model["image_size"] // model["patch_size"]) ** 2


def layer_fwd_flops(model: dict, width: float) -> float:
    """Forward matmul FLOPs of one encoder layer for one sample."""
    T, D, hd = tokens(model), model["d_model"], model["head_dim"]
    h, f = kept(model, width)
    a = h * hd
    qkv = 2 * T * D * 3 * a
    attn = 2 * 2 * T * T * a           # scores and the weighted sum
    out = 2 * T * a * D
    mlp = 2 * 2 * T * D * f
    return float(qkv + attn + out + mlp)


def embed_flops(model: dict) -> float:
    p = model["patch_size"]
    return float(2 * tokens(model) * p * p * 3 * model["d_model"])


def head_flops(model: dict) -> float:
    """Pooled [D] x [D, C] matmul of one sample (forward)."""
    return float(2 * model["d_model"] * model["n_classes"])


def sample_step_flops(model: dict, depth: int, width: float,
                      available: bool) -> float:
    """Required FLOPs of one sample through one TPGF local step."""
    L = model["n_layers"]
    prefix_fwd = embed_flops(model) + depth * layer_fwd_flops(model, width)
    prefix_bwd = embed_flops(model) + 2 * depth * layer_fwd_flops(model,
                                                                   width)
    total = prefix_fwd + 3 * head_flops(model) + prefix_bwd
    if available:
        suffix = (L - depth) * layer_fwd_flops(model, 1.0)
        total += 3 * suffix + 3 * head_flops(model) + prefix_bwd
    return total


def layer_params(model: dict, width: float) -> int:
    D, hd = model["d_model"], model["head_dim"]
    h, f = kept(model, width)
    a = h * hd
    return 3 * D * a + a * D + D * f + f + f * D + D + 4 * D


def embed_params(model: dict) -> int:
    p, D = model["patch_size"], model["d_model"]
    return p * p * 3 * D + D + tokens(model) * D


def head_params(model: dict) -> int:
    return model["d_model"] * model["n_classes"] + model["n_classes"]


def round_work(model: dict, traffic: dict, groups, avail):
    """(required FLOPs, required bytes, real sample-steps) of one round.

    ``groups``: [(depth, width, ids)] for every kernel launch of the round;
    ``avail``: [N] bool server availability of the round."""
    B, steps = traffic["batch_size"], traffic["local_steps"]
    L = model["n_layers"]
    image_bytes = 4 * model["image_size"] ** 2 * 3 + 4
    flops = bytes_ = 0.0
    samples = 0
    for depth, width, ids in groups:
        client_bytes = 4 * (embed_params(model)
                            + depth * layer_params(model, width)
                            + head_params(model))
        server_bytes = 4 * ((L - depth) * layer_params(model, 1.0)
                            + head_params(model))
        for i in ids:
            flops += B * steps * sample_step_flops(model, depth, width,
                                                   bool(avail[i]))
        samples += B * steps * len(ids)
        bytes_ += steps * (len(ids) * (2 * client_bytes + B * image_bytes)
                           + 2 * server_bytes)
    return flops, bytes_, samples

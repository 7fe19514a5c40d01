"""Shared benchmark config: one reduced-scale federated setting.

Paper scale (ViT-16, CIFAR, 50-100 clients, A100s) is scaled to this
container (1 CPU core): ViT family reduced to 6 layers / d_model 64 on a
16x16 synthetic-CIFAR with the SAME protocol (Dirichlet alpha=0.5 non-IID,
mem~U[2,16] GB, lat~U[20,200] ms heterogeneity, Eq.1 allocation). Trends,
not absolute numbers, are the reproduction target (EXPERIMENTS.md).
"""
from __future__ import annotations

import time

from repro.configs import base


def sim_config(**kw):
    cfg = base.get_reduced("vit16_cifar").replace(
        n_layers=6, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, image_size=16, n_classes=10)
    return cfg.replace(**kw) if kw else cfg


def make_trainer(method: str, *, n_clients: int = 16, seed: int = 0,
                 availability: float = 1.0, cfg=None, alpha: float = 0.2,
                 lr: float = 0.25, local_steps: int = 3,
                 batch_size: int = 32, noise: float = 0.7):
    from repro.federated.round import FederatedTrainer
    return FederatedTrainer(cfg or sim_config(), n_clients, method,
                            seed=seed, lr=lr, local_steps=local_steps,
                            batch_size=batch_size, availability=availability,
                            alpha=alpha, noise=noise)


def make_engine(strategy: str, *, n_clients: int = 16, seed: int = 0,
                availability: float = 1.0, sample_frac: float = 1.0,
                optimizer="sgd", cfg=None, alpha: float = 0.2,
                lr: float = 0.25, local_steps: int = 3,
                batch_size: int = 32, noise: float = 0.7):
    """Engine-native variant of ``make_trainer`` exposing the scenario
    knobs the old trainer API could not (sample_frac, optimizer)."""
    from repro.federated import Engine
    return Engine(cfg or sim_config(), n_clients, strategy,
                  seed=seed, lr=lr, local_steps=local_steps,
                  batch_size=batch_size, availability=availability,
                  sample_frac=sample_frac, optimizer=optimizer,
                  alpha=alpha, noise=noise)


def run_until(trainer, *, max_rounds: int, target: float = None,
              eval_every: int = 1):
    """Returns (history of (round, acc), rounds_to_target or None)."""
    curve = []
    hit = None
    for r in range(max_rounds):
        trainer.run_round()
        if (r + 1) % eval_every == 0:
            acc = trainer.evaluate()
            curve.append((r + 1, acc))
            if target is not None and hit is None and acc >= target:
                hit = r + 1
                break
    return curve, hit


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0


def time_call(fn, *args, repeat: int = 3, **kw):
    import jax
    jax.block_until_ready(fn(*args, **kw))  # warm up / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        r = fn(*args, **kw)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / repeat * 1e6  # us

"""Everything a run feeds the system, made from ``--seed`` by the benchmark.

The program under test and the plain reference both take their inputs from
here, so the reference never reads anything the program made:

* weights: one jitted call on the device, in the layout the engine holds
  (``params`` tree + stacked ``[N, ...]`` local heads), float32, from the
  shapes the configuration's family states (``bench/families/``);
* the federated dataset: the family's synthetic samples, made in bulk with
  numpy, split over the clients by Dirichlet(alpha) — a copy of the
  program's own split (``repro.data.synthetic``), kept here so a program
  change cannot move it;
* the fleet: each client's depth and width tier, stated as data in the
  traffic mix;
* the server-availability and batch-index streams, as seeds; the draw
  order is documented in :func:`batch_indices`.

Seeds may be any non-negative integer, also above 32 bits: each
stream is derived with ``numpy.random.SeedSequence([seed, stream])``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# stream ids under one --seed
_WEIGHTS, _DATA, _PARTITION, _AVAIL, _BATCH = 1, 2, 3, 4, 5


def stream_seed(seed: int, stream: int) -> int:
    """A 63-bit integer seed for one named stream of ``seed``."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return int(state[0]) << 31 | int(state[1]) >> 1


def jax_key(seed: int):
    """A raw threefry key (uint32[2]) for the weight stream of ``seed``."""
    import jax.numpy as jnp
    state = np.random.SeedSequence([int(seed), _WEIGHTS]).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def avail_seed(seed: int) -> int:
    return stream_seed(seed, _AVAIL)


def batch_seed(seed: int) -> int:
    return stream_seed(seed, _BATCH)


# ------------------------------------------------------------------ weights

def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(specs, seed: int, dtype="float32"):
    """The weights a family's ``weight_shapes`` describes, on the default
    device, in ONE jitted call: normal(0, std) matrices, zero biases, unit
    norm scales."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (shape, init) in zip(keys, flat):
            if init == "zeros":
                out.append(jnp.zeros(shape, dt))
            elif init == "ones":
                out.append(jnp.ones(shape, dt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * init).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(jax_key(seed))


# --------------------------------------------------------------------- data

@dataclasses.dataclass
class Shard:
    """One client's samples (the ``images``/``labels`` the engine reads)."""
    images: np.ndarray
    labels: np.ndarray


@dataclasses.dataclass
class Dataset:
    images: np.ndarray        # [S, ...] float32 inputs, client shards in order
    labels: np.ndarray        # [S] int32
    offsets: np.ndarray       # [N] first flat index of each client's shard
    sizes: np.ndarray         # [N] shard sizes

    def shards(self):
        return [Shard(self.images[o:o + s], self.labels[o:o + s])
                for o, s in zip(self.offsets, self.sizes)]

    def engine_data(self):
        """The ``data=`` dict ``Engine`` takes (``clients`` is all a
        training round reads)."""
        return {"clients": self.shards(), "test": None}


def make_dataset(family, model: dict, traffic: dict, seed: int) -> Dataset:
    """The family's synthetic samples (``family.make_samples``, from the
    data stream), split over the clients by Dirichlet(alpha) and laid out
    shard after shard."""
    n_clients = traffic["n_clients"]
    rng = np.random.default_rng(stream_seed(seed, _DATA))
    images, labels = family.make_samples(model, traffic, rng)
    shards = dirichlet_partition(labels, n_clients, traffic["dirichlet_alpha"],
                                 stream_seed(seed, _PARTITION))
    order = np.concatenate(shards)
    sizes = np.array([len(s) for s in shards], np.int64)
    return Dataset(images[order].astype(np.float32),
                   labels[order].astype(np.int32),
                   np.concatenate([[0], np.cumsum(sizes)[:-1]]), sizes)


def dirichlet_partition(labels, n_clients: int, alpha: float, seed: int,
                        min_per_client: int = 2):
    """Class-skewed client shards: per class, Dirichlet(alpha) proportions
    over the clients; a client left with fewer than ``min_per_client``
    samples is topped up at random."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    shards = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            shards[i].extend(part.tolist())
    out = []
    for s in shards:
        if len(s) < min_per_client:
            s = s + rng.choice(len(labels), min_per_client - len(s)).tolist()
        out.append(np.array(sorted(s), np.int64))
    return out


# -------------------------------------------------------------------- fleet

@dataclasses.dataclass
class Fleet:
    depths: np.ndarray        # [N] int
    widths: np.ndarray        # [N] float, width tier

    def cohorts(self):
        """[(depth, [(width, ids)])]: depths ascending, width groups
        ascending, ids ascending — the order a round trains them in."""
        out = []
        for d in sorted(set(self.depths.tolist())):
            ids = np.where(self.depths == d)[0]
            groups = []
            for w in sorted(set(self.widths[ids].tolist())):
                groups.append((w, ids[self.widths[ids] == w]))
            out.append((int(d), groups))
        return out


def make_fleet(traffic: dict) -> Fleet:
    """The fleet the traffic mix states: each client's depth and width
    tier, as data (``fleet.depths``, ``fleet.widths``). The engine
    allocates its own from ``fleet_seed``; the harness checks that the two
    agree (``run.build``)."""
    f = traffic["fleet"]
    if not len(f["depths"]) == len(f["widths"]) == traffic["n_clients"]:
        raise ValueError("the traffic's fleet does not hold one depth and "
                         "one width per client")
    return Fleet(np.asarray(f["depths"], np.int32),
                 np.asarray(f["widths"], np.float64))


# ---------------------------------------------------------------- streams

def availability(seed: int, n_clients: int, rounds: int, fraction: float):
    """[rounds, N] bool: i.i.d. Bernoulli(fraction) server availability,
    one ``random(N)`` draw per round from the availability stream."""
    rng = np.random.default_rng(avail_seed(seed))
    return np.stack([rng.random(n_clients) < fraction
                     for _ in range(rounds)])


def batch_indices(rng, data: Dataset, ids, steps: int, batch: int):
    """[steps, len(ids), batch] flat sample indices for one width group,
    drawn step-major, client-minor: one ``integers(0, size_i, batch)`` per
    (step, client), offset into the client's shard. A round draws its
    groups in :meth:`Fleet.cohorts` order from one stream."""
    out = np.empty((steps, len(ids), batch), np.int64)
    for s in range(steps):
        for j, i in enumerate(ids):
            out[s, j] = data.offsets[i] + rng.integers(0, data.sizes[i],
                                                       batch)
    return out

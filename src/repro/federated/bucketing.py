"""Padded cohort buckets: the bounded-compile contract for strategy kernels.

jit specializes on shapes, so a kernel called with a ``[Nc, ...]`` client
stack compiles once per distinct cohort size — and per-round participation
churn (sample_frac, Markov arrivals) plus HASFL re-tuning make Nc different
nearly every round, so compile count grows with the number of *distinct
cohort sizes ever seen*. Bucketing rounds every cohort up to a small ladder
(powers of two by default): a cohort of 5 runs in the size-8 kernel with
three padded slots. Depth is a RUNTIME kernel argument (masked scan over
the full layer stack, ``model.run_stack``), so compile count is
O(widths x buckets) regardless of fleet composition — independent of how
many distinct depth tiers exist or how HASFL re-tuning reshuffles them.

Padded-slot contract (every strategy kernel obeys it):
  * slot ids beyond the real cohort are the SENTINEL ``n_clients`` — an
    out-of-range row index. jax clamps out-of-bounds *gathers* (the slot
    reads some real client's data, which it never publishes) and drops
    out-of-bounds *scatters* (the slot's outputs are discarded), so padding
    needs no masking at the read/write boundary.
  * ``valid`` ([bucket] bool) masks every cross-slot reduction inside the
    kernel: a padded slot contributes zero gradient to the pooled server
    mean, zero loss weight, and — because ``avail`` is forced False on
    padded slots — can never unfreeze the server branch.

Multi-device fleet execution: kernels register as :class:`FleetKernel`
objects that pair the replicated jit with per-mesh ``shard_map`` variants
over the bucket-slot axis. Bucket sizes round up to a multiple of the
fleet-mesh data extent (``bucket_size(..., multiple_of=)``) so every shard
owns whole slots; cross-slot reductions inside kernels go through
:func:`slot_sum` / :func:`masked_slot_mean` / :func:`freeze_gate`, which
``psum`` over the fleet axis when the kernel runs shard-mapped — the same
padded-slot contract holds shard-locally, and the pooled means / freeze
gates see the whole bucket.

Compile accounting: kernels register here (``register_kernel``) and
``kernel_compiles()`` sums their jit cache sizes (replicated + every
sharded variant), so tests and benchmarks can assert the bounded-compile
property directly.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_LADDER: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)


def bucket_size(n: int, ladder: Sequence[int] = None, *,
                multiple_of: int = 1) -> int:
    """Smallest ladder entry >= ``n`` (doubling past the ladder top).

    ``ladder=None`` means the default power-of-two ladder; an ``"exact"``
    ladder (used by the benchmark's pre-refactor reference mode) is spelled
    ``bucket_size(n, ladder=())`` — no padding, one compile per size.

    ``multiple_of`` rounds the bucket up so it divides evenly into that
    many shards (the fleet-mesh data extent): shard_map needs whole slots
    per shard, and padded slots are a numerical no-op anyway, so a size-5
    cohort on an 8-device fleet mesh runs in a size-8 bucket with one slot
    per device.
    """
    if ladder is None:
        ladder = DEFAULT_LADDER
    b = None
    for cand in ladder:
        if cand >= n:
            b = int(cand)
            break
    if b is None:
        b = int(ladder[-1]) if len(ladder) else n
        while b < n:
            b *= 2
    if multiple_of > 1 and b % multiple_of:
        b += multiple_of - b % multiple_of
    return b


def pad_ids(ids: np.ndarray, bucket: int, n_clients: int) -> np.ndarray:
    """[bucket] int32 ids, padded with the out-of-range sentinel
    ``n_clients`` (dropped by scatters, clamped by gathers)."""
    out = np.full(bucket, n_clients, np.int32)
    out[:len(ids)] = ids
    return out


def pad_rows(arr: np.ndarray, bucket: int, fill=0) -> np.ndarray:
    """Pad axis 0 of a per-slot host array up to ``bucket``."""
    if len(arr) == bucket:
        return arr
    pad = np.full((bucket - len(arr),) + arr.shape[1:], fill, arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def pad_slot_axis(arr: np.ndarray, bucket: int, axis: int) -> np.ndarray:
    """Pad the slot axis of a host array (e.g. [steps, Nc, B] batch
    indices) up to ``bucket`` with zeros (a valid gather index; the data it
    fetches is never used)."""
    if arr.shape[axis] == bucket:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, bucket - arr.shape[axis])
    return np.pad(arr, widths)


# ------------------------------------------------- sharded slot reductions
#
# Every cross-slot reduction inside a strategy kernel goes through these
# helpers. Replicated execution (axis_name=None) reduces over the local
# slot axis only; under a shard-mapped kernel the fleet axis name is bound
# and the local partial reduces ``psum`` across shards, so the result is
# identical-by-construction on every device and the padded-slot contract
# (zero gradient, zero loss weight, cannot unfreeze the server) holds for
# the WHOLE bucket, not just the local shard.

def slot_sum(x, axis_name=None):
    """Sum over the slot axis (0), across all fleet shards."""
    s = jnp.sum(x, axis=0)  # fleetlint: disable=FL002 — this IS the blessed primitive the rule routes to
    return jax.lax.psum(s, axis_name) if axis_name is not None else s


def masked_slot_mean(tree, valid, axis_name=None):
    """Mean of ``tree`` leaves over the VALID slots of the whole bucket.
    ``valid`` is the [local slots] bool mask; padded slots contribute zero
    to the numerator (where, not multiply: NaN-safe) and nothing to the
    denominator."""
    n = slot_sum(valid.astype(jnp.float32), axis_name)

    def mean(g):
        row = valid.reshape((-1,) + (1,) * (g.ndim - 1))
        return slot_sum(jnp.where(row, g, 0.0), axis_name) / n

    return jax.tree.map(mean, tree)


def freeze_gate(avail, valid, axis_name=None):
    """``any(avail & valid)`` over the whole bucket — the server freeze
    gate. A padded slot (valid=False) can never unfreeze the server, on
    any shard."""
    hit = jnp.any(avail & valid)  # fleetlint: disable=FL002 — freeze_gate is the blessed gate; valid already ANDed in
    if axis_name is not None:
        hit = jax.lax.psum(hit.astype(jnp.int32), axis_name) > 0
    return hit


# ------------------------------------------------------------ sanitizer mode

# True only while FleetKernel.sanitized() traces its checkified variant —
# guard_gather reads it at trace time, so the normal jit never carries the
# check ops (and never pays for them).
_SANITIZE_TRACE = False


def guard_gather(idx, size: int, what: str = "batch gather"):
    """Under the sanitizer trace, assert an on-device gather is in bounds.

    jax *clamps* out-of-bounds gathers silently — the padded-slot contract
    depends on that for slot-id gathers, but the batch gather (sample
    indices into the flat dataset) must always be in range, padded slots
    included (``pad_rows`` fills with index 0). ``checkify.index_checks``
    cannot instrument it (its grad-of-gather transpose is broken), so
    kernels call this at the gather site instead; it is a no-op outside
    sanitize mode.
    """
    if _SANITIZE_TRACE:
        from jax.experimental import checkify
        ok = jnp.all((idx >= 0) & (idx < size))  # fleetlint: disable=FL002 — not a slot gate: ANY slot's OOB index (pads included) must trip
        checkify.check(ok, f"{what}: index out of bounds [0, {int(size)})")


class SlotSanitizerError(RuntimeError):
    """A checkify-instrumented kernel tripped a float/index check.

    ``slots`` is the tuple of bucket-slot indices whose outputs came back
    non-finite — the per-slot attribution that turns "a NaN appeared
    somewhere in the cohort" into "client in slot 3 diverged". Empty when
    the failure left no non-finite trace in slot-leading outputs (e.g. an
    out-of-bounds gather caught before it corrupted anything).
    """

    def __init__(self, message: str, slots=()):
        super().__init__(message)
        self.slots = tuple(slots)


def _nonfinite_slots(out, bucket: int):
    """Bucket-slot indices with any non-finite value in a slot-leading
    output leaf. Host-side by design: the sanitizer path trades the
    one-host-sync contract for attribution."""
    bad = set()
    for leaf in jax.tree_util.tree_leaves(out):
        if (getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == bucket
                and np.issubdtype(np.asarray(leaf).dtype, np.floating)):
            rows = np.asarray(leaf).reshape(bucket, -1)
            bad |= {int(i) for i in
                    np.nonzero(~np.isfinite(rows).all(axis=1))[0]}
    return sorted(bad)


def sanitize_failure(err, out, bucket: int, *, kernel: str = "kernel"):
    """Raise :class:`SlotSanitizerError` if the checkify error ``err`` is
    set, attributing the failure to bucket slots via ``out``."""
    msg = err.get()
    if msg is None:
        return
    slots = _nonfinite_slots(out, bucket)
    where = f" (bucket slots {slots})" if slots else ""
    raise SlotSanitizerError(f"sanitizer tripped in {kernel}{where}: {msg}",
                             slots)


# ------------------------------------------------------- compile accounting

_KERNELS: List = []


class FleetKernel:
    """A registered strategy kernel: the replicated jit plus lazily built
    per-mesh ``shard_map`` variants over the bucket-slot axis.

    ``impl(*statics, *arrays, axis_name=None)`` is the pure kernel body:
    the first ``n_static`` positional arguments are jit-static (cfg,
    optimizer, steps, width — depth rides as a runtime array argument),
    the rest are array pytrees whose slot axis (if any)
    is described by ``specs(axes, *arrays) -> (in_specs, out_specs)`` —
    PartitionSpec trees sharding slot-leading axes over the fleet mesh axes
    and replicating shared state (server params, the flat dataset).
    ``axis_name`` is None under the replicated jit and the fleet axis names
    under a sharded variant, so the kernel's cross-slot reductions
    (:func:`slot_sum` & co.) span the whole bucket either way.

    Calling the kernel runs the replicated jit — drop-in for the PR-3
    calling convention; ``Engine.kernel_fn`` picks :meth:`sharded` when a
    fleet mesh with data extent > 1 is configured.
    """

    def __init__(self, impl: Callable, n_static: int, specs: Callable):
        self.impl = impl
        self.n_static = n_static
        self.specs = specs
        self._jit = jax.jit(_named(functools.partial(impl, axis_name=None),
                                   impl),
                            static_argnums=tuple(range(n_static)))
        self._sharded = {}
        self._sanitized = None
        functools.update_wrapper(self, impl)

    def __call__(self, *args):
        return self._jit(*args)

    def lower(self, *args):
        """The replicated jit's ``jax.stages.Lowered`` for ``args`` (arrays
        or ``ShapeDtypeStruct``s): ``.compile()`` it to read the program's
        HLO and memory analysis without running it."""
        return self._jit.lower(*args)

    def sharded(self, mesh):
        """The shard-mapped variant for ``mesh`` (cached per mesh)."""
        key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
        fn = self._sharded.get(key)
        if fn is None:
            fn = self._sharded[key] = self._build_sharded(mesh)
        return fn

    def _build_sharded(self, mesh):
        from repro.launch.sharding import fleet_axes
        axes = fleet_axes(mesh)
        ns, impl, specs = self.n_static, self.impl, self.specs

        def mapped(*args):
            statics, arrays = args[:ns], args[ns:]
            in_specs, out_specs = specs(axes, *arrays)
            body = functools.partial(impl, *statics, axis_name=axes)
            return jax.shard_map(lambda *a: body(*a), mesh=mesh,
                                 in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False)(*arrays)

        jitted = jax.jit(_named(mapped, impl),
                         static_argnums=tuple(range(ns)))

        def run(*args):
            # canonicalize placement BEFORE the jit boundary: the jit
            # cache keys on argument shardings, so round-to-round drift
            # (fresh numpy uploads vs committed outputs of the previous
            # round) would re-specialize the same (width, bucket) program.
            # device_put to the kernel's own specs is a no-op when already
            # placed and keeps the compile count at one per static key.
            statics, arrays = args[:ns], args[ns:]
            in_specs, _ = specs(axes, *arrays)
            return jitted(*statics, *_place(arrays, in_specs, mesh))

        run._cache_size = jitted._cache_size
        return run

    def sanitized(self):
        """The checkify-instrumented replicated jit (built on first use).

        Wraps the pure impl in ``checkify.checkify`` with float checks
        (NaN/inf anywhere in the kernel) and index checks (out-of-bounds
        on the on-device batch gather), so a call returns ``(err, out)``
        instead of ``out``. Always the replicated variant — sanitize mode
        is a debug tool, and checkify's error plumbing does not compose
        with ``shard_map``'s out_specs; under a fleet mesh the sanitizer
        still sees the whole bucket, just on one device.
        """
        if self._sanitized is None:
            from jax.experimental import checkify
            impl = self.impl

            def traced(*args):
                # flag guard_gather sites on for the duration of THIS trace
                global _SANITIZE_TRACE
                prev, _SANITIZE_TRACE = _SANITIZE_TRACE, True
                try:
                    return impl(*args, axis_name=None)
                finally:
                    _SANITIZE_TRACE = prev

            # index_checks is deliberately absent: its instrumentation of
            # the grad-of-gather transpose raises IndexError on the loss
            # gather (take_along_axis under value_and_grad); the explicit
            # guard_gather user check covers the OOB surface instead.
            fn = checkify.checkify(
                traced,
                errors=checkify.float_checks | checkify.user_checks)
            self._sanitized = jax.jit(
                _named(fn, impl), static_argnums=tuple(range(self.n_static)))
        return self._sanitized

    def _cache_size(self) -> int:
        return (self._jit._cache_size()
                + (self._sanitized._cache_size() if self._sanitized else 0)
                + sum(f._cache_size() for f in self._sharded.values()))


def _named(fn, impl):
    """``fn`` under ``impl``'s name, so its jit compiles as
    ``jit_<impl name>`` (a bare ``functools.partial`` compiles as
    ``jit__unknown``) and the profiler's trace shows which kernel ran."""
    fn.__name__ = fn.__qualname__ = impl.__name__
    return fn


def _place(arrays, in_specs, mesh):
    """Device_put the kernel arguments to their PartitionSpecs (each a
    prefix ``P`` covering its whole arg, or a pytree of per-leaf ``P``s)
    in ONE batched transfer."""
    from jax.sharding import NamedSharding, PartitionSpec
    per_arg, shardings = [], []
    for arg, spec in zip(arrays, in_specs):
        leaves, treedef = jax.tree_util.tree_flatten(arg)
        if isinstance(spec, PartitionSpec):
            shardings += [NamedSharding(mesh, spec)] * len(leaves)
        else:
            shardings += [NamedSharding(mesh, s) for s in
                          jax.tree_util.tree_leaves(
                              spec,
                              is_leaf=lambda s: isinstance(s,
                                                           PartitionSpec))]
        per_arg.append((leaves, treedef))
    placed = iter(jax.device_put([x for ls, _ in per_arg for x in ls],
                                 shardings))
    return tuple(jax.tree_util.tree_unflatten(td, [next(placed) for _ in ls])
                 for ls, td in per_arg)


def register_kernel(fn=None, *, n_static: int = 4, specs: Callable = None):
    """Register a strategy kernel for compile accounting.

    Two forms:
      * bare ``@register_kernel`` over an already-jitted function — the
        PR-3 form, replicated execution only;
      * ``@register_kernel(n_static=..., specs=...)`` over a pure impl
        (``axis_name``-aware) — wraps it in a :class:`FleetKernel` whose
        sharded variants ``Engine(mesh=...)`` dispatches to.
    """
    if fn is not None:
        _KERNELS.append(fn)
        return fn

    def deco(impl):
        k = FleetKernel(impl, n_static, specs)
        _KERNELS.append(k)
        return k

    return deco


def kernel_compiles() -> int:
    """Total compiled specializations across all registered kernels (the
    number the bounded-compile tests pin) — replicated jits plus every
    per-mesh sharded variant. Uses the jit cache size, so deltas around a
    run count that run's fresh compiles."""
    return sum(k._cache_size() for k in _KERNELS)

"""App-axis (and trial-axis) data parallelism for batched array programs.

The experiment engine treats "application" as a leading batch axis: every
heavy dispatch (census evaluation, memo fills, k-means fits, Monte-Carlo
trials) is a vmapped program over ``(A, ...)`` stacks. This module turns
those same programs into device-parallel ones by ``shard_map``-ping the app
axis over a 1-D ``("app",)`` mesh (see ``repro.launch.mesh.make_app_mesh``).

Per-app results are bit-identical to the single-device vmap: lanes never
communicate, so sharding only changes *where* a lane runs. The app axis is
padded up to the device count by edge-replication (recomputing a real app
is always numerically safe; padded rows are dropped on return).

The streaming Monte-Carlo engine adds a second mesh dimension: a 2-D
``("app", "trial")`` mesh (``repro.launch.mesh.make_app_trial_mesh``)
splits each trial *chunk* across the trial axis on top of the app split.
``make_app_trial_sharded`` is the generalized wrapper: inputs still shard
over the app axis only (tables are per-app state; each trial-device
derives its own draws from the shared PRNG-block contract), while the
trial axis appears in the *outputs* — additive ``TrialStats``
accumulators arrive pre-merged by an in-program ``psum`` over the trial
axis (the cross-device coverage/CI merge: every leaf is a sum, so
sharded totals equal single-device totals exactly for the integer
leaves), and optional dense per-trial stacks re-assemble along it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def app_axis_name(mesh: Mesh) -> str:
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"app sharding expects a 1-D mesh, got axes {mesh.axis_names}")
    return mesh.axis_names[0]


def app_trial_axes(mesh: Mesh) -> tuple[str, "str | None"]:
    """(app_axis, trial_axis) names of a trial-engine mesh.

    Accepts the 1-D ``("app",)`` mesh (trial axis ``None`` — every device
    evaluates full chunks) and the 2-D ``("app", "trial")`` mesh (chunks
    split across the second axis). Axis order is positional: the leading
    axis shards apps, the trailing one trials.
    """
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0], None
    if len(mesh.axis_names) == 2:
        return mesh.axis_names[0], mesh.axis_names[1]
    raise ValueError(
        f"trial sharding expects a 1-D ('app',) or 2-D ('app', 'trial') "
        f"mesh, got axes {mesh.axis_names}")


def pad_app_axis(arr, multiple: int):
    """Pad the leading axis to a multiple by edge-replicating the last row."""
    a = arr.shape[0]
    pad = (-a) % multiple
    if pad == 0:
        return arr
    reps = np.concatenate([np.arange(a), np.full(pad, a - 1)])
    return arr[reps] if isinstance(arr, np.ndarray) else \
        jax.numpy.take(arr, jax.numpy.asarray(reps), axis=0)


def make_app_sharded(fn: Callable, mesh: Mesh,
                     replicated: Sequence[int] = ()) -> Callable:
    """Wrap a batched-over-app ``fn`` so its app axis runs device-parallel.

    ``fn`` takes arrays whose leading axis is the app axis (except argument
    positions in ``replicated``, which are broadcast — e.g. a config
    matrix) and returns a pytree of arrays sharded the same way. The
    wrapper pads the app axis to the app-axis size, dispatches one
    ``shard_map``-ped program, and trims the padding. On a 2-D
    ``("app", "trial")`` mesh only the app axis is used — the program is
    replicated along the trial axis (trial parallelism is the streaming
    trial engine's job, via ``make_app_trial_sharded``).
    """
    axis, _ = app_trial_axes(mesh)
    n_dev = int(mesh.shape[axis])
    rep = frozenset(replicated)

    @functools.lru_cache(maxsize=8)
    def build(n_args: int):
        in_specs = tuple(P() if i in rep else P(axis) for i in range(n_args))
        # lanes are independent, so the varying-axis check is vacuous
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=P(axis), check_vma=False))

    def call(*args: Any):
        a_size = next(np.shape(a)[0] for i, a in enumerate(args)
                      if i not in rep)
        padded = tuple(a if i in rep else pad_app_axis(a, n_dev)
                       for i, a in enumerate(args))
        out = build(len(args))(*padded)
        return jax.tree.map(lambda o: o[:a_size], out)

    return call


@functools.lru_cache(maxsize=None)
def app_sharded_cached(fn: Callable, mesh: Mesh,
                       replicated: tuple = ()) -> Callable:
    """Memoized ``make_app_sharded`` for module-level fns (stable hash)."""
    return make_app_sharded(fn, mesh, replicated)


def make_app_trial_sharded(fn: Callable, mesh: Mesh,
                           replicated: Sequence[int] = (),
                           *, out_specs) -> Callable:
    """``make_app_sharded`` generalized to ``("app", "trial")`` meshes.

    Inputs follow the app contract exactly — leading-axis arrays shard
    over the app axis (positions in ``replicated`` broadcast) and the
    app axis pads to the mesh's app-axis size by edge replication. The
    differences serve the streaming trial programs:

    * ``out_specs`` is caller-supplied (a pytree prefix over ``fn``'s
      outputs): a streaming program returns mixed layouts — per-app
      accumulators (``P(app)``, replicated over the trial axis after the
      in-program ``psum`` merge) next to optional dense chunk stacks
      assembled over both axes (``P(None, app, trial)``).
    * the outputs keep the app padding: the app axis is not leading in
      every output, and the caller drops the padding where it fetches
      the outputs to the host.

    ``fn`` itself may read ``jax.lax.axis_index`` of either axis to pick
    its shard of the work — see ``repro.experiments.montecarlo``.
    """
    app, _ = app_trial_axes(mesh)
    n_app = int(mesh.shape[app])
    rep = frozenset(replicated)

    @functools.lru_cache(maxsize=8)
    def build(n_args: int):
        in_specs = tuple(P() if i in rep else P(app) for i in range(n_args))
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def call(*args: Any):
        padded = tuple(a if i in rep else pad_app_axis(a, n_app)
                       for i, a in enumerate(args))
        return build(len(args))(*padded)

    return call

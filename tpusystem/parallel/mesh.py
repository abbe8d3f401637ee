"""Device meshes — the TPU replacement for CUDA device strings.

The reference resolves ``Depends(device)`` to ``'cuda'``
(``examples/tinysys/main.py:36-37``); here the injected runtime fact is a
:class:`jax.sharding.Mesh` laid out over the chip topology. All parallelism
(DP/FSDP/TP/PP/SP/EP) is expressed as named mesh axes; GSPMD and
``shard_map`` insert the matching ICI/DCN collectives.

Axis vocabulary (used by every sharding policy and model in the framework):

======== ========================================================
``data``   pure data parallelism (gradient all-reduce)
``fsdp``   fully-sharded data parallelism (params/opt-state scatter)
``model``  tensor parallelism (weight-matrix column/row split)
``seq``    sequence/context parallelism (ring attention)
``expert`` expert parallelism (MoE all-to-all dispatch)
``stage``  pipeline parallelism (collective-permute between stages)
======== ========================================================

A :class:`MeshSpec` is a registered entity: its axis sizes capture into the
experiment identity hash, so checkpoints distinguish incompatible layouts
(SURVEY.md §7.3 "identity under sharding").
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpusystem.registry import register

DATA, FSDP, MODEL, SEQ, EXPERT, STAGE = 'data', 'fsdp', 'model', 'seq', 'expert', 'stage'
AXES = (DATA, FSDP, MODEL, SEQ, EXPERT, STAGE)


def on_tpu() -> bool:
    """The one "are we on the chip" predicate: kernels pick compiled vs
    interpret mode, and the serving levers pick their defaults, from
    this and nothing else."""
    return jax.default_backend() == 'tpu'


def force_host_platform(n_devices: int = 8) -> None:
    """Force JAX onto the host (CPU) platform with ``n_devices`` virtual chips.

    The standard way to exercise mesh/collective code (DP/FSDP/TP/PP/SP/EP)
    without TPU hardware: the test suite and ``dryrun_multichip`` both run on
    a virtual CPU mesh set up by this call, which sets the device-count
    flag and pins ``jax_platforms`` to ``cpu``.

    Must be called before the first JAX backend initialization in the
    process — XLA reads ``--xla_force_host_platform_device_count`` once, at
    backend creation. Raises RuntimeError (rather than leaving a silently
    single-device mesh) when called too late.
    """
    import os
    import re
    flag = '--xla_force_host_platform_device_count'
    flags = os.environ.get('XLA_FLAGS', '')
    if flag in flags:
        # Replace a stale preset count (e.g. from the caller's environment)
        # rather than silently keeping it when it is smaller than requested.
        current = re.search(rf'{flag}=(\d+)', flags)
        if current and int(current.group(1)) < n_devices:
            flags = re.sub(rf'{flag}=\d+', f'{flag}={n_devices}', flags)
            os.environ['XLA_FLAGS'] = flags
    else:
        os.environ['XLA_FLAGS'] = (flags + f' {flag}={n_devices}').strip()
    jax.config.update('jax_platforms', 'cpu')
    have = len(jax.devices('cpu'))
    if have < n_devices:
        raise RuntimeError(
            f'need {n_devices} virtual CPU devices but found {have}: a JAX '
            f'backend was already initialized in this process, so '
            f'{flag} cannot take effect. '
            f'Call force_host_platform() before any JAX operation, or run '
            f'in a fresh process with XLA_FLAGS={flag}={n_devices}.')


@register
class MeshSpec:
    """Declarative mesh layout: axis name -> size.

    Size ``-1`` on exactly one axis means "fill with all remaining devices".
    Axes of size 1 are kept in the mesh (they cost nothing and keep
    PartitionSpecs uniform across configurations).

    Example::

        MeshSpec(data=-1, model=4).build()   # v4-32: data=8 x model=4
        MeshSpec(fsdp=-1).build()            # pure FSDP over every chip
    """

    def __init__(self, data: int = 1, fsdp: int = 1, model: int = 1,
                 seq: int = 1, expert: int = 1, stage: int = 1):
        self.sizes = {DATA: data, FSDP: fsdp, MODEL: model,
                      SEQ: seq, EXPERT: expert, STAGE: stage}

    def resolved_sizes(self, device_count: int) -> dict[str, int]:
        sizes = dict(self.sizes)
        wildcards = [axis for axis, size in sizes.items() if size == -1]
        if len(wildcards) > 1:
            raise ValueError(f'only one axis may be -1, got {wildcards}')
        fixed = math.prod(size for size in sizes.values() if size != -1)
        if wildcards:
            if device_count % fixed:
                raise ValueError(
                    f'{device_count} devices not divisible by fixed axes {fixed}')
            sizes[wildcards[0]] = device_count // fixed
        elif fixed != device_count:
            raise ValueError(
                f'mesh wants {fixed} devices but {device_count} are available')
        return sizes

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        sizes = self.resolved_sizes(len(devices))
        shape = tuple(sizes[axis] for axis in AXES)
        return Mesh(np.asarray(devices).reshape(shape), AXES)

    def resized(self, device_count: int) -> 'MeshSpec':
        """The same layout policy scaled to a new device count — the mesh
        derivation of an elastic resize (:mod:`tpusystem.parallel.elastic`).

        A wildcard spec (one axis ``-1``) already scales: the wildcard
        re-fills over the new count. A fully pinned spec scales its
        ``data`` axis — or ``fsdp`` when the data axis cannot absorb the
        change — keeping ``model``/``seq``/``expert``/``stage`` fixed:
        those axis sizes encode kernel and memory-layout choices a resize
        must not silently change. Raises ``ValueError`` when no data-like
        axis divides the new count (resize to a compatible world or
        restart with a new spec deliberately).
        """
        sizes = dict(self.sizes)
        if any(size == -1 for size in sizes.values()):
            spec = MeshSpec(**sizes)
            spec.resolved_sizes(device_count)     # validate divisibility now
            return spec
        for axis in (DATA, FSDP):
            fixed = math.prod(size for name, size in sizes.items()
                              if name != axis)
            if device_count % fixed == 0:
                return MeshSpec(**{**sizes, axis: device_count // fixed})
        raise ValueError(
            f'cannot rescale mesh {sizes} to {device_count} devices: '
            f'neither the data nor the fsdp axis divides the new count')


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """A 1x1x1x1x1x1 mesh over one chip — the degenerate case that keeps
    every sharding annotation valid on a single device."""
    devices = [device] if device is not None else jax.devices()[:1]
    return MeshSpec().build(devices)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Canonical global-batch sharding: the batch dimension splits over the
    combined (data, fsdp) axes — FSDP is data parallelism for activations."""
    return NamedSharding(mesh, PartitionSpec((DATA, FSDP)))


def scan_carry_constraint(mesh: Mesh | None):
    """Sharding pin for a scan-over-layers carry ``[batch, seq, dim]``
    in the TP x FSDP composition: batch over ``data``, hidden dim over
    ``fsdp``.

    Without a pin, GSPMD gives the scan carry a batch-over-(data, fsdp)
    layout at the loop boundary while the body's FSDP-scattered weight
    grads want the carry dim-sharded — an unplannable transition that
    falls back to an involuntary full rematerialization per layer
    (spmd_partitioner.cc 'last resort' replicate-then-repartition).
    Pinning the carry to P(data, None, fsdp) matches the layout the
    partitioner itself targets inside the body — measured 2 warnings ->
    0 on a 2x2x2 mesh, identical loss. Returns an identity function for
    ``mesh=None`` or meshes without both axes active (GSPMD's own choice
    is already transition-free there). Used by both LM families'
    ``scan_layers`` paths."""
    import jax

    if mesh is None:
        return lambda hidden: hidden
    shape = dict(mesh.shape)
    if shape.get(FSDP, 1) < 2 or shape.get(MODEL, 1) < 2:
        return lambda hidden: hidden
    sharding = NamedSharding(mesh, PartitionSpec(DATA, None, FSDP))
    return lambda hidden: jax.lax.with_sharding_constraint(hidden, sharding)


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for ``[steps, batch, ...]`` stacks (the
    :func:`tpusystem.train.build_multi_step` input): the steps axis stays
    whole on every device, the batch axis (dim 1) splits over
    (data, fsdp) like :func:`batch_sharding`."""
    return NamedSharding(mesh, PartitionSpec(None, (DATA, FSDP)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())

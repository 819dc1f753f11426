"""Activation-sharding policy: explicit with_sharding_constraint hooks.

GSPMD propagates weight shardings to most activations, but a few reshapes
(GQA head grouping, logits) lose the head/model dimension and silently
replicate multi-GiB temporaries.  Models call ``shard(x, axes)`` with
symbolic axes; when a policy is active (launch/dryrun/train set it), the
constraint is applied with divisibility checks; with no policy it's a no-op
(CPU unit tests, single device).

Symbolic axes: "fsdp" -> ("pod","data") / ("data",), "model" -> "model",
None -> replicated.

The same state says whether the code being traced belongs to a program
partitioned over a mesh (``partitioned``): under ``activation_sharding``,
or under ``suspended(partitioned=True)`` as the serving placement's jits
trace.  Code with no partitioning rule (a Pallas call, which the
partitioner would gather onto every device) asks it.
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def _current():
    return getattr(_STATE, "policy", None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, seq_shard: bool = False):
    """Enable activation constraints for code traced within this context."""
    fsdp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    prev = _current(), partitioned()
    _STATE.policy = {"mesh": mesh, "fsdp": fsdp, "seq_shard": seq_shard}
    _STATE.partitioned = True
    try:
        yield
    finally:
        _STATE.policy, _STATE.partitioned = prev


@contextlib.contextmanager
def suspended(partitioned: bool = False):
    """Deactivate any ambient policy for code traced within this context;
    ``partitioned`` says whether that code is a program partitioned over a
    mesh (the serving placement's jits with explicit shardings) or one on a
    single device.

    The serving placement layer (serving/placement.py) shards through
    explicit jit in/out shardings instead of activation constraints, and it
    must NOT inherit a policy leaked from an enclosing dryrun/train scope:
    an active policy flips MoE onto the capacity-bounded expert-parallel
    path (models/moe.py), where prefill bucket padding competes with real
    tokens for expert capacity and token streams stop being batch-invariant.
    """
    prev = _current(), getattr(_STATE, "partitioned", False)
    _STATE.policy, _STATE.partitioned = None, partitioned
    try:
        yield
    finally:
        _STATE.policy, _STATE.partitioned = prev


def partitioned() -> bool:
    """Whether the code being traced belongs to a program partitioned over
    a mesh (see the module docstring)."""
    return getattr(_STATE, "partitioned", False)


def _resolve(mesh, fsdp, axes, shape):
    spec = []
    for dim, ax in zip(shape, axes):
        if ax is None:
            spec.append(None)
            continue
        real = fsdp if ax == "fsdp" else (ax,) if isinstance(ax, str) else ax
        size = math.prod(mesh.shape[a] for a in real)
        spec.append(real if dim % size == 0 else None)
    return P(*spec)


def shard(x: jax.Array, axes) -> jax.Array:
    """Constrain ``x`` to symbolic ``axes`` (len == x.ndim) if policy active."""
    pol = _current()
    if pol is None:
        return x
    spec = _resolve(pol["mesh"], pol["fsdp"], axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pol["mesh"], spec))


def seq_sharded() -> bool:
    pol = _current()
    return bool(pol and pol["seq_shard"])


def divides(axis: str, dim: int) -> bool:
    """True if `dim` can shard over `axis` under the active policy (False
    when no policy: callers then skip layout specialization)."""
    pol = _current()
    if pol is None:
        return False
    real = pol["fsdp"] if axis == "fsdp" else (axis,)
    return dim % math.prod(pol["mesh"].shape[a] for a in real) == 0

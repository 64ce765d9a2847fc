"""Accelerator -> host completion synchronization (paper §II credit counter).

Manticore baseline: the host busy-polls each cluster's done flag — O(M) host
interactions.  The paper adds a *credit counter*: the host arms a threshold,
every cluster atomically increments the counter when done, and the unit fires
one interrupt when the threshold is reached — O(1) for the host.

JAX analogues:

  * ``PollingSync`` (baseline): the host blocks on every addressable shard of
    every output leaf, one after the other — O(num_devices) host round-trips.
  * ``CreditCounterSync``: the compiled step emits an extra *credits* output —
    a one-int32-per-device sharded vector, all-reduced to a replicated scalar.
    Each device "increments the counter" by contributing its element to the
    reduction; the scalar becomes ready only when every device has finished
    its shard. The host blocks on that single 4-byte scalar — the interrupt.

``credits`` doubles as a health check: each device's credit is gated on its
local outputs being finite, so ``credits < threshold`` signals a poisoned
(NaN/Inf) shard and triggers the fault-tolerance path (see repro.runtime).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


class FaultDetected(RuntimeError):
    """Credits below threshold: some device produced non-finite outputs."""


def _flat_spec(mesh: jax.sharding.Mesh) -> NamedSharding:
    """One credit slot per device: a vector sharded over every mesh axis.

    The sharding lives on an ``Auto``-typed view of the mesh's devices, so
    the constraint holds whatever axis types the caller's mesh has
    (``jax.make_mesh`` makes ``Explicit`` axes, which a sharding
    constraint may not name).
    """
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return NamedSharding(auto, P(mesh.axis_names))


def credit_threshold(mesh: jax.sharding.Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def emit_credits(outputs: Any, mesh: jax.sharding.Mesh) -> jax.Array:
    """Build the credit-counter reduction inside a jitted step.

    Produces a replicated int32 scalar equal to the number of devices iff all
    floating-point outputs are finite. Structurally this compiles to each
    device contributing one int32 (its credit) followed by an all-reduce —
    the distributed form of the paper's centralized counter.
    """
    ok = jnp.bool_(True)
    for leaf in jax.tree.leaves(outputs):
        if isinstance(leaf, jax.Array | jnp.ndarray) and jnp.issubdtype(
                leaf.dtype, jnp.floating):
            ok &= jnp.isfinite(leaf).all()
    n = credit_threshold(mesh)
    ones = jnp.ones((n,), jnp.int32) * ok.astype(jnp.int32)
    ones = jax.lax.with_sharding_constraint(ones, _flat_spec(mesh))
    return jnp.sum(ones)  # all-reduce -> replicated scalar ("the counter")


def attach_credits(step_fn: Callable, mesh: jax.sharding.Mesh) -> Callable:
    """Wrap a step function so it additionally returns the credit scalar."""

    def wrapped(*args, **kwargs):
        out = step_fn(*args, **kwargs)
        return out, emit_credits(out, mesh)

    return wrapped


class CreditCounterSync:
    """Host side of the credit counter: one blocking read of one scalar."""

    name = "credit_counter"

    def __init__(self, mesh: jax.sharding.Mesh):
        self.mesh = mesh
        self.threshold = credit_threshold(mesh)

    def wait(self, credits: jax.Array) -> int:
        got = int(credits)  # single 4-byte device->host readback ("IRQ")
        if got != self.threshold:
            raise FaultDetected(
                f"credit counter read {got}, expected {self.threshold}: "
                "a device produced non-finite outputs")
        return got

    def timed_wait(self, credits: jax.Array) -> tuple[int, float]:
        """wait() plus the measured host-side blocking time in seconds.

        The elapsed time is the step's completion latency as seen by the
        host — the measurement the serving calibrator
        (repro.serve.calibrator) refits the runtime model from.
        """
        t0 = time.perf_counter()
        got = self.wait(credits)
        return got, time.perf_counter() - t0

    def host_interactions(self) -> int:
        return 1


class PollingSync:
    """Baseline: block on every output shard sequentially (O(M) host work)."""

    name = "polling"

    def __init__(self, mesh: jax.sharding.Mesh):
        self.mesh = mesh

    def wait(self, outputs: Any) -> int:
        polls = 0
        for leaf in jax.tree.leaves(outputs):
            if not isinstance(leaf, jax.Array):
                continue
            for shard in leaf.addressable_shards:
                shard.data.block_until_ready()  # one poll per device shard
                polls += 1
        return polls

    def host_interactions(self) -> int:
        return len(self.mesh.devices.flatten())


SYNCS = {"credit_counter": CreditCounterSync, "polling": PollingSync}

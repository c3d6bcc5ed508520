"""Device and process meshes. Functions, not module-level constants —
importing this module never touches jax device state (device count is
locked at first backend init)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    axes = ("data", "model")
    return jax.make_mesh((data, model), axes, axis_types=_auto(axes))


def _auto(axes: tuple) -> tuple:
    """Auto (compiler-propagated) axis types. ``jax.make_mesh`` defaults to
    Explicit axes, under which the embed gather ``embed[tokens]`` of a
    ``P(model, data)`` table by ``data``-sharded tokens has no legal output
    sharding (``DuplicateSpecError``); the models place activations with
    ``with_sharding_constraint`` and leave the rest to propagation."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


# ---------------------------------------------------------------------------
# process placement (multi-process cluster runtime, launch/runtime.py)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ProcSlot:
    """One logical position in the process grid: a master shard or one
    replica of a slave shard. ``replica`` is None for masters (masters are
    cold-backed by checkpoints, not replicated)."""

    role: str                 # "master" | "slave"
    shard_id: int
    replica: Optional[int] = None

    @property
    def name(self) -> str:
        if self.role == "master":
            return f"master-{self.shard_id}"
        return f"slave-{self.shard_id}.{self.replica}"


@dataclass(frozen=True)
class ProcessMesh:
    """The process-grid analogue of the device mesh: masters along one
    axis, (slave shard x replica) along the other two. The runtime spawns
    one OS process per slot; elastic replica add/remove appends or drops
    slots on the replica axis only (shard axes are fixed by the routing
    plan's partition congruence)."""

    num_master: int
    num_slave: int
    num_replicas: int

    def masters(self) -> list[ProcSlot]:
        return [ProcSlot("master", m) for m in range(self.num_master)]

    def slaves(self) -> list[ProcSlot]:
        return [ProcSlot("slave", s, r) for s in range(self.num_slave)
                for r in range(self.num_replicas)]

    def slots(self) -> list[ProcSlot]:
        return self.masters() + self.slaves()


def make_process_mesh(num_master: int, num_slave: int,
                      num_replicas: int = 1) -> ProcessMesh:
    assert num_master >= 1 and num_slave >= 1 and num_replicas >= 1
    return ProcessMesh(num_master, num_slave, num_replicas)

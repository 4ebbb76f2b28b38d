"""Symbol routing across engine shards — the rule, shared with the mesh engine.

The reference's parallelism axis is per-symbol independence (every Redis key
is symbol-prefixed; SURVEY §2.1). Scaling beyond one chip/host therefore
needs no collectives at all: partition symbols across shards and route each
order to its owner — the EP-style routing of SURVEY §2.1/§5.8. The sharded
engine itself is BatchEngine under a mesh (engine.batch, parallel.mesh):
one engine, its lanes dealt over the chips. What is here is the rule it
deals them by, for code that routes outside the engine:

  ShardRouter      — symbol -> shard by engine.placement, the rule the
                     mesh engine places its lanes by: symbols dealt
                     round-robin in arrival order (adding hosts is a
                     controlled resharding, never implicit). fnv1a stays
                     here for the fleet tier (fleet/router.py), whose
                     partitions are named across processes.
  multihost_mesh   — jax.distributed + a global 1-D symbol mesh for the
                     single-process-per-host deployment where one engine
                     spans hosts via jax.sharding (chips linked by
                     ICI/DCN; XLA partitions the batched step with zero
                     collectives, mesh.py).
"""

from __future__ import annotations

from ..engine import placement
from ..engine.host import Interner


def fnv1a(s: str) -> int:
    """Stable 64-bit FNV-1a (Python's hash() is salted per process — useless
    for cross-host agreement)."""
    h = 0xCBF29CE484222325
    for b in s.encode():
        h ^= b
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


class ShardRouter:
    """symbol -> shard, by the placement rule of the mesh engine
    (engine.placement.shard_of): the k-th symbol this router meets goes to
    shard k mod n_shards. The arrival order is the router's state, as the
    interner is the engine's; one router fronts one set of shards."""

    def __init__(self, n_shards: int):
        if n_shards <= 0:
            raise ValueError("n_shards must be positive")
        self.n_shards = n_shards
        self._arrivals = Interner()

    def route(self, symbol: str) -> int:
        return placement.shard_of(
            self._arrivals.intern(symbol) - 1, self.n_shards
        )


def multihost_mesh(n_local: int | None = None):
    """Global 1-D symbol mesh across all participating hosts' devices.

    Single-host (and test) environments get the local mesh. Multi-host
    requires jax.distributed.initialize() to have run (coordinator env);
    afterwards jax.devices() spans hosts, ICI/DCN routing is XLA's problem,
    and the batched step shards with zero collectives exactly as on one
    chip.
    """
    import jax

    from .mesh import make_mesh

    return make_mesh(n_local if n_local is not None else len(jax.devices()))

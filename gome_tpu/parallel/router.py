"""Symbol-hash routing across engine shards — the multi-host dispatch layer.

The reference's parallelism axis is per-symbol independence (every Redis key
is symbol-prefixed; SURVEY §2.1). Scaling beyond one chip/host therefore
needs no collectives at all: partition symbols across engine shards and
route each order to its owner — the EP-style routing of SURVEY §2.1/§5.8.
Cross-shard traffic exists only here, at dispatch (DCN between hosts, PCIe
to chips); matching never communicates.

Topology:
  ShardRouter      — symbol -> shard by engine.placement, the rule the
                     mesh engine places its lanes by: symbols dealt
                     round-robin in arrival order (adding hosts is a
                     controlled resharding, never implicit). fnv1a stays
                     here for the fleet tier (fleet/router.py), whose
                     partitions are named across processes.
  ShardedEngine    — N MatchEngine shards behind the single-engine facade:
                     mark/process split per shard, events merged back into
                     arrival order. In-process stand-in for N per-host
                     engine services; the wire variant routes to N doOrder
                     queues (one per shard service) with the same mapping.
  multihost_mesh   — jax.distributed + a global 1-D symbol mesh for the
                     single-process-per-host deployment where one engine
                     spans hosts via jax.sharding instead of N independent
                     shards (chips linked by ICI/DCN; XLA partitions the
                     batched step with zero collectives, mesh.py).
"""

from __future__ import annotations

from ..engine import placement
from ..engine.book import BookConfig
from ..engine.host import Interner
from ..engine.orchestrator import MatchEngine
from ..types import MatchResult, Order


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


class ShardedEngine:
    """N engine shards behind the MatchEngine facade. Correctness argument:
    a symbol maps to exactly one shard, so per-symbol op order is preserved
    by construction; shards share nothing, so processing order across
    shards is free (SURVEY §5.2's serialized-per-symbol invariant)."""

    def __init__(
        self,
        n_shards: int,
        config: BookConfig | None = None,
        n_slots: int = 128,
        max_t: int = 32,
        kernel: str = "scan",
        engine_factory=None,
    ):
        self.router = ShardRouter(n_shards)
        factory = engine_factory or (
            lambda i: MatchEngine(
                config=config, n_slots=n_slots, max_t=max_t, kernel=kernel
            )
        )
        self.shards = [factory(i) for i in range(n_shards)]

    def mark(self, order: Order) -> None:
        self.shards[self.router.route(order.symbol)].mark(order)

    def unmark(self, order: Order) -> None:
        self.shards[self.router.route(order.symbol)].unmark(order)

    def process(self, orders: list[Order]) -> list[MatchResult]:
        """Apply one micro-batch across shards; returns the event stream in
        the EXACT single-FIFO global emission order of the reference
        consumer (rabbitmq.go:116-125): each shard processes its sub-batch
        tagged with global arrival indices (one device call per shard, full
        batching preserved) and the per-order event groups merge back by
        arrival."""
        by_shard: dict[int, list[tuple[int, Order]]] = {}
        for i, order in enumerate(orders):
            by_shard.setdefault(self.router.route(order.symbol), []).append(
                (i, order)
            )
        merged: list[tuple[int, list[MatchResult]]] = []
        for shard_id, items in by_shard.items():
            merged.extend(self.shards[shard_id].process_indexed(items))
        merged.sort(key=lambda kv: kv[0])
        return [ev for _, evs in merged for ev in evs]

    def process_columnar(self, orders: list[Order]):
        """Columnar facade parity with MatchEngine (the consumer publishes
        through the EventBatch surface; the wrapper provides it)."""
        return _ResultsBatch(self.process(orders))

    def process_frame(self, cols: dict):
        """ORDER-frame ingestion on the in-process sharded facade: decodes
        to Orders and runs the exact object path — admission semantics
        included (per-shard columnar splitting with per-shard interner
        tables is not worth the complexity here; sharded DEPLOYMENTS route
        frames to per-shard doOrder queues upstream, so each shard's
        consumer gets whole frames and the native frame pipeline)."""
        from ..engine.frames import orders_from_frame

        return _ResultsBatch(self.process(orders_from_frame(cols)))

    def process_with_arrival_order(
        self, orders: list[Order]
    ) -> list[MatchResult]:
        """Kept for API compatibility: process() itself now emits exact
        global-FIFO order (per-order arrival tags), so this is an alias."""
        return self.process(orders)

    @property
    def stats(self):
        return [s.stats for s in self.shards]


class _ResultsBatch:
    """list[MatchResult] with the minimal EventBatch surface the consumer's
    publish path uses (len, to_results, to_json_lines, seq0)."""

    seq0 = None  # unstamped; the consumer passes seq0 explicitly

    def __init__(self, results):
        self._results = results

    def __len__(self):
        return len(self._results)

    def to_results(self):
        return list(self._results)

    def to_json_lines(self, seq0=None):
        import dataclasses

        from ..bus import encode_match_result

        if seq0 is None:
            return [encode_match_result(r) for r in self._results]
        return [
            encode_match_result(dataclasses.replace(r, seq=seq0 + i))
            for i, r in enumerate(self._results)
        ]


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

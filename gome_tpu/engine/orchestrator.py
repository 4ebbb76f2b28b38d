"""The engine facade: pre-pool admission + batched device matching.

This is the TPU framework's equivalent of the reference's `engine` package
surface — the layer the gateway and the order consumer talk to
(gomengine/engine/engine.go:35-54 + the pre-pool protocol,
gomengine/engine/nodepool.go:14-28, gomengine/main.go:44-45):

  gateway side   mark(order)      — HSET S:comparison S:U:O 1 (main.go:44-45)
  consumer side  process(orders)  — the consumer loop body (engine.go:46-54):
                   ADD: consumed only if still marked, else dropped
                        (engine.go:58-62; the cancel-before-consume race,
                        SURVEY §2.3.3)
                   DEL: clears the mark first so a still-queued ADD dies
                        (engine.go:88-90), then cancels on the book

The pre-pool is shared state between gateway and consumer (Redis in the
reference); here it is an in-process set — single-binary deployments share
the MatchEngine instance. Deployments that need the race semantics to
survive restart snapshot `pre_pool` alongside the books via the durability
layer (gome_tpu.persist).
"""

from __future__ import annotations

import numpy as np

from ..types import Action, MatchResult, Order
from ..utils.tracing import span
from .batch import BatchEngine, EngineStats, is_device_fault
from .book import BookConfig
from .prepool import consume_batch_of, make_prepool


def _kept_rows(cols: dict, keep) -> dict:
    """An ORDER frame's columns without the rows admission dropped (the
    dictionaries stay whole: the index columns still point into them)."""
    if keep.all():
        return cols
    return dict(
        cols,
        n=int(keep.sum()),
        **{
            k: np.ascontiguousarray(cols[k][keep])
            for k in (
                "action", "side", "kind", "price", "volume",
                "symbol_idx", "uuid_idx", "oids",
            )
        },
    )


class MatchEngine:
    """Admission + matching for one engine shard (a set of symbol lanes).

    Orders enter twice, like the reference's two process hops: `mark()` when
    the gateway accepts an ADD (before it is queued), `process()` when the
    consumer drains a micro-batch from the queue. Cancels are never marked
    (main.go:54-64 sets no pre-pool entry).
    """

    def __init__(
        self,
        config: BookConfig | None = None,
        n_slots: int = 1024,
        max_t: int = 32,
        auto_grow: bool = True,
        kernel: str = "scan",
        **batch_kw,
    ):
        """batch_kw passes through to BatchEngine (mesh, dense,
        dense_t_max, max_slots, max_cap, pallas_interpret)."""
        self.batch = BatchEngine(
            config or BookConfig(),
            n_slots,
            max_t=max_t,
            auto_grow=auto_grow,
            kernel=kernel,
            **batch_kw,
        )
        # The marker store shared with the gateway. In-process by default
        # (C++-backed when the toolchain allows — prepool.NativePrePool);
        # split-process deployments assign a prepool.RespPrePool here (and
        # in the gateway process) so the markers live in a Redis-compatible
        # server exactly as the reference's do (nodepool.go:14-28).
        self.pre_pool = make_prepool()

    # -- gateway side ------------------------------------------------------
    def mark(self, order: Order) -> None:
        """Record "submitted, not yet consumed/cancelled" for an ADD
        (nodepool.go:14-16). No-op for other actions."""
        if order.action is Action.ADD:
            self.pre_pool.add(self._prekey(order))

    def unmark(self, order: Order) -> None:
        """Discard an order's pre-pool entry without processing it — the
        consumer's dead-letter path uses this so a poisoned ADD's restored
        mark does not linger forever (and leak into snapshots)."""
        self.pre_pool.discard(self._prekey(order))

    def mark_frame(self, cols: dict) -> None:  # gomelint: hotpath
        """Bulk mark for the columnar admit path: one fused pass over an
        ORDER block's columns (ADD rows only — the pool implementations
        share that contract with mark())."""
        self.pre_pool.mark_frame(cols)

    def unmark_frame(self, cols: dict) -> None:
        """Bulk undo of mark_frame — the columnar emit-failure path."""
        self.pre_pool.unmark_frame(cols)

    # -- consumer side -----------------------------------------------------
    def process(self, orders: list[Order]) -> list[MatchResult]:
        """Apply one micro-batch in arrival order; returns the MatchResult
        event stream in the reference's global emission order. Admission
        (the pre-pool check, engine.go:58-62) drops ADDs cancelled before
        consumption without touching the book."""
        return self.process_columnar(orders).to_results()

    def process_one(self, order: Order) -> list[MatchResult]:
        return self.process([order])

    def process_columnar(self, orders: list[Order]):
        """process() as a columnar EventBatch (gome_tpu.engine.events) —
        the shape the consumer publishes from without building per-event
        objects. A convenience over process_frame on the orders' columns
        (the exact, synchronous form): same admission, and a raised batch
        restores the pre-pool marks it consumed."""
        from ..bus.colwire import orders_to_cols

        return self.process_frame(orders_to_cols(orders), fast=False)

    # -- views -------------------------------------------------------------
    @property
    def stats(self) -> EngineStats:
        """Single source of truth: the BatchEngine's counters (the facade
        adds only dropped_no_prepool to the same object)."""
        return self.batch.stats

    @property
    def config(self) -> BookConfig:
        return self.batch.config

    @property
    def books(self):
        return self.batch.books

    def process_frame(self, cols: dict, fast: bool = True):
        """Columnar-frame ingestion (bus.colwire ORDER frames): admission
        (the pre-pool check, engine.go:58-62: unmarked ADDs drop, DELs
        clear their marks) applied by filtering the columns, then the
        frame packer (engine.frames) runs the batch. Returns an
        EventBatch. fast=True uses the device-side event-compaction path
        (one fetch per frame; transparently falls back to the exact
        escalating path when a device budget trips); fast=False the
        exact path itself. A raised frame restores the marks it consumed
        — the at-least-once consumer replays failed batches, and a
        replayed ADD must not die as unmarked just because the failed
        attempt already popped its key. For cross-frame pipelining use
        engine.pipeline.FramePipeline."""
        from . import frames

        cols, consumed = self.admit_frame(cols)
        run = frames.apply_frame_fast if fast else frames.process_frame
        try:
            return run(self.batch, cols)
        except Exception:
            self.pre_pool |= consumed
            raise

    def admit_frame(self, cols: dict) -> tuple[dict, set]:
        """Frame admission: returns (filtered columns, the consumed marks)
        — the caller restores `consumed` (pre_pool |= consumed) if the
        batch later fails (at-least-once replay must not drop re-admitted
        ADDs)."""
        with span("frame_admit", frame=cols.get("frame"),
                  orders=int(cols["n"])):
            return self._admit_frame(cols)

    def _admit_frame(self, cols: dict) -> tuple[dict, set]:
        consume_frame = getattr(self.pre_pool, "consume_frame", None)
        if consume_frame is not None:
            # Fused native pass: compose keys + pop markers + masks in C++.
            keep, consumed = consume_frame(cols)
            dropped = int(
                ((cols["action"] == int(Action.ADD)) & ~keep).sum()
            )
            self.stats.dropped_no_prepool += dropped
            return _kept_rows(cols, keep), consumed

        n = int(cols["n"])
        action = cols["action"].tolist()
        syms, uuids = cols["symbols"], cols["uuids"]
        sidx, uidx = cols["symbol_idx"].tolist(), cols["uuid_idx"].tolist()
        oid_list = [o.decode() for o in cols["oids"].tolist()]
        consumed: set[tuple[str, str, str]] = set()
        ADD, DEL = int(Action.ADD), int(Action.DEL)
        # Key construction at C speed: list-comp indexing + zip tuples;
        # symbol/uuid string objects are shared (hashes cached), only the
        # oid hash is fresh per order. Marks consume through ONE batched
        # call — a single pipelined round trip when the pool is remote.
        keys = list(
            zip((syms[k] for k in sidx), (uuids[k] for k in uidx), oid_list)
        )
        sel = [i for i, a in enumerate(action) if a == ADD or a == DEL]
        existed = consume_batch_of(
            self.pre_pool,
            keys if len(sel) == n else [keys[i] for i in sel],
        )
        keep = np.zeros(n, bool)  # NOP padding never reaches the device
        dropped = 0
        for i, ex in zip(sel, existed):
            if action[i] == ADD:
                if ex:
                    keep[i] = True
                    consumed.add(keys[i])
                else:
                    dropped += 1
            else:  # DEL: always admitted; a consumed mark kills a queued ADD
                keep[i] = True
                if ex:
                    consumed.add(keys[i])
        self.stats.dropped_no_prepool += dropped
        return _kept_rows(cols, keep), consumed

    # -- geometry persistence ----------------------------------------------
    def save_geometry(self, path: str) -> None:
        """Persist the flow's shape manifest (grow-only geometry floors +
        every dispatched fast-path shape combo) as JSON. A later process
        load_geometry()s it so its first live frame runs with zero
        first-seen traces — the deployment-side answer to 'per-process
        re-traces amortizing out' (pairs with the XLA persistent compile
        cache, which covers compiles but not traces)."""
        import json
        import os

        m = self.batch.shape_manifest()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(m, f)
        os.replace(tmp, path)  # atomic: readers never see a torn file

    def load_geometry(
        self, path: str, precompile: bool = True, presize_cap: bool = True
    ) -> int:
        """Load a persisted shape manifest: prewarm the grow-only floors
        (so this process CHOOSES the recorded shapes) and, by default,
        replay the recorded combos with all-padding inputs (so they are
        traced+compiled before live traffic). Returns the number of combos
        replayed (0 with precompile=False or an absent/invalid file —
        loading is best-effort: geometry is a performance hint, never
        state). A compile or device error while replaying is not a stale
        manifest and propagates (engine.batch.is_device_fault)."""
        import json

        from . import frames

        try:
            try:
                f = open(path)
            except FileNotFoundError:
                return 0  # no manifest yet: the normal first-boot case
            with f:
                m = json.load(f)
            floors = m["floors"]
            combos = m["combos"]
            as_int = lambda d: {int(k): int(v) for k, v in d.items()}
            # Pre-size storage to the flow's recorded stationary cap:
            # boots pay ONE up-front grow instead of a mid-traffic
            # escalate+replay, and the deep-cap combos become replayable.
            # presize_cap=False keeps boot storage (shallow flows through
            # the same engine then run at their own cheaper cap; combos
            # above it are skipped and compile from the persistent cache
            # when escalation genuinely happens).
            if presize_cap and floors.get("cap"):
                # Clamp to this engine's max_cap: a manifest from a
                # bigger deployment must degrade (shallower presize,
                # deep combos skipped), never abort the whole load.
                self.batch.ensure_cap(
                    min(int(floors["cap"]), self.batch.max_cap)
                )
            self.batch.prewarm_geometry(
                rows_floor=as_int(floors.get("rows_floor", {})),
                t_floor=as_int(floors.get("t_floor", {})),
                fills_buf=as_int(floors.get("fills_buf", {})),
                cancels_buf=as_int(floors.get("cancels_buf", {})),
            )
            if not precompile:
                for combo in combos:
                    self.batch.record_combo(combo)
                return 0
            return frames.precompile_combos(self.batch, combos)
        except Exception as e:
            if is_device_fault(e):
                raise
            # Best-effort end to end: a stale manifest (combo layout from
            # an older version, shapes recorded before an n_slots growth)
            # must never stop a boot — it is a performance hint, never
            # state. Whatever floors merged before the failure stand
            # (grow-only, still valid). But never SILENTLY: a swallowed
            # failure here cost two full bench rounds of mid-region
            # compiles before anyone noticed.
            from ..utils.logging import get_logger

            get_logger("engine").warning(
                "geometry manifest %s not applied: %s", path, e
            )
            return 0

    @staticmethod
    def _prekey(order: Order) -> tuple[str, str, str]:
        """S:comparison field = S:U:O (ordernode.go:89-92)."""
        return (order.symbol, order.uuid, order.oid)

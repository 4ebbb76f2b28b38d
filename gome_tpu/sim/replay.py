"""Deterministic seeded replay: manifests, digests, and GCO record mode.

A run manifest is `(seed, config, n_steps)` plus a canonical-JSON
config hash — enough to regenerate a flow bit-exactly in any process
(the generator is a pure function of the PRNG key and static config;
XLA CPU/TPU executables are deterministic for this integer program).
`run_from_manifest` replays one and folds the whole trade stream + final
book state into a sha256 digest, so two processes can assert bit-exact
equality without shipping trajectories around.

Record mode dumps each step's generated background grid as a GCO ORDER
frame (bus.colwire) — the exact wire form the service path consumes —
so a sim run can be re-fed through gateway→bus→consumer for cross-stack
validation (tests/test_sim.py does, via engine.frames.orders_from_frame
+ MatchEngine admission).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.book import BookConfig
from .env import EnvConfig, _env_step_impl, env_reset, null_action, rollout
from .flow import FlowConfig, gen_ops

MANIFEST_VERSION = 1


# -- manifest ---------------------------------------------------------------

def config_dict(config: EnvConfig) -> dict:
    """JSON-able canonical form of an EnvConfig (dtype by name)."""
    return {
        "flow": dataclasses.asdict(config.flow),
        "book": {
            "cap": config.book.cap,
            "max_fills": config.book.max_fills,
            "dtype": np.dtype(config.book.dtype).name,
        },
        "n_agent_ops": config.n_agent_ops,
        "obs_levels": config.obs_levels,
        "agent_uid": config.agent_uid,
    }


def config_digest(config: EnvConfig) -> str:
    blob = json.dumps(
        config_dict(config), sort_keys=True, separators=(",", ":")
    )
    return hashlib.sha256(blob.encode()).hexdigest()


def make_manifest(config: EnvConfig, seed: int, n_steps: int) -> dict:
    """The (seed, config hash, step count) record that pins one run."""
    return {
        "version": MANIFEST_VERSION,
        "seed": int(seed),
        "n_steps": int(n_steps),
        "config": config_dict(config),
        "config_sha256": config_digest(config),
    }


def env_config_from_manifest(manifest: dict) -> EnvConfig:
    """Rebuild the EnvConfig and verify the manifest's config hash (a
    hand-edited manifest must fail loudly, not replay something else)."""
    if manifest.get("version") != MANIFEST_VERSION:
        raise ValueError(
            f"unsupported sim manifest version {manifest.get('version')!r}"
        )
    c = manifest["config"]
    config = EnvConfig(
        flow=FlowConfig(**c["flow"]),
        book=BookConfig(
            cap=c["book"]["cap"],
            max_fills=c["book"]["max_fills"],
            dtype=jnp.dtype(c["book"]["dtype"]),
        ),
        n_agent_ops=c["n_agent_ops"],
        obs_levels=c["obs_levels"],
        agent_uid=c["agent_uid"],
    )
    digest = config_digest(config)
    if digest != manifest["config_sha256"]:
        raise ValueError(
            f"sim manifest config hash mismatch: manifest says "
            f"{manifest['config_sha256'][:12]}…, config rebuilds to "
            f"{digest[:12]}…"
        )
    return config


def run_from_manifest(manifest: dict) -> dict:  # gomelint: hotpath
    """Replay a manifest (background flow only) and digest the result.

    The digest folds the per-step fill-stream checksums (env.StepInfo)
    and every leaf of the final book state — any divergence anywhere in
    the trade sequence or book evolution changes it. One compiled scan,
    one device fetch at the end."""
    config = env_config_from_manifest(manifest)
    state, _ = env_reset(config, jax.random.PRNGKey(manifest["seed"]))
    final, (_rewards, info) = rollout(config, state, manifest["n_steps"])
    checks, trades, events, b_over, f_over = jax.device_get(
        (info.checksum, info.trades, info.events, info.book_overflow,
         info.fill_overflow)
    )
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(checks).tobytes())
    for leaf in jax.device_get(final.books):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return {
        "digest": h.hexdigest(),
        "n_steps": int(manifest["n_steps"]),
        "events": int(events.sum()),
        "trades": int(trades.sum()),
        "book_overflow": int(b_over.sum()),
        "fill_overflow": int(f_over.sum()),
    }


# -- grid -> host columns / orders ------------------------------------------

def grid_to_columns(ops: dict, drop_misses: bool = False) -> dict:
    """One host-side `[S, T]` op grid (numpy leaves, DeviceOp field names)
    to service-wire columns (the bench/_svc_gateway_step contract).

    Occupied cells are linearized in (t, lane) order — a grid column is
    one arrival instant across lanes, so t-major order is a faithful
    serial stream for the per-lane FIFO semantics. `drop_misses` removes
    deliberate-miss cancels (oid handle 0) for consumers that track oid
    liveness (the service pre-pool)."""
    t_idx, lane_idx = np.nonzero(np.asarray(ops["action"]).T != 0)
    pick = lambda f: np.asarray(ops[f])[lane_idx, t_idx]
    action = pick("action")
    oid_num = pick("oid").astype(np.int64)
    if drop_misses:
        keep = ~((action == 2) & (oid_num == 0))
        lane_idx, t_idx = lane_idx[keep], t_idx[keep]
        action = action[keep]
        oid_num = oid_num[keep]
    uid = pick("uid").astype(np.int64)
    return dict(
        n=len(action),
        action=action.astype(np.uint8),
        side=pick("side").astype(np.uint8),
        kind=pick("kind").astype(np.uint8),
        price=pick("price").astype(np.int64),
        volume=pick("volume").astype(np.int64),
        symbol_idx=lane_idx.astype(np.uint32),
        # Background uids are 1..n_uids -> dictionary indices 0-based.
        uuid_idx=np.maximum(uid - 1, 0).astype(np.uint32),
        oids=np.char.add("o", oid_num.astype("U20")).astype("S"),
    )


def orders_from_grid(ops: dict, drop_misses: bool = False) -> list:
    """Host-side grid -> Order objects (for the oracle-parity fuzz
    harness). Symbols are "s{lane}", uuids "u{idx}", oids "o{handle}"."""
    from ..types import Action, Order, OrderType, Side

    cols = grid_to_columns(ops, drop_misses=drop_misses)
    out = []
    for i in range(cols["n"]):
        out.append(Order(
            uuid=f"u{int(cols['uuid_idx'][i])}",
            oid=cols["oids"][i].decode(),
            symbol=f"s{int(cols['symbol_idx'][i])}",
            side=Side(int(cols["side"][i])),
            price=int(cols["price"][i]),
            volume=int(cols["volume"][i]),
            action=Action(int(cols["action"][i])),
            order_type=OrderType(int(cols["kind"][i])),
        ))
    return out


# -- GCO record mode --------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def _record_step(config: EnvConfig, state):  # gomelint: disable=GL903 — offline record tool: one compile per config, paid at session start before any frame traffic; not a frame-dispatch combo, so the boot replay can't (and needn't) reach it
    """One background-only env transition that ALSO returns the generated
    grid. gen_ops is pure in (flow state, books), so re-deriving the grid
    here is bit-identical to the one `_env_step_impl` applies (and XLA
    CSEs the duplicate trace)."""
    _, bg_ops = gen_ops(config.flow, state.flow, state.books)
    state2, _obs, _reward, info = _env_step_impl(
        config, state, null_action(config)
    )
    return state2, bg_ops, info


# gomelint: hotpath
def record_frames(
    config: EnvConfig, seed: int, n_steps: int
) -> list[bytes]:
    """Replay `n_steps` of background flow, dumping each step's grid as
    one GCO ORDER frame (empty steps are skipped). The frames re-feed
    the service path: decode_order_frame -> admission -> device.

    One batched `jax.device_get` per step (the sanctioned fetch — this
    is the record path, not the rollout loop)."""
    from ..bus.colwire import encode_order_frame

    symbols = [f"s{i}" for i in range(config.flow.n_lanes)]
    uuids = [f"u{i}" for i in range(config.flow.n_uids)]
    state, _ = env_reset(config, jax.random.PRNGKey(seed))
    frames: list[bytes] = []
    for _ in range(n_steps):
        state, bg_ops, _info = _record_step(config, state)
        host = jax.device_get(bg_ops)
        cols = grid_to_columns(host._asdict())
        if cols["n"] == 0:
            continue
        frames.append(encode_order_frame(
            cols["n"], cols["action"], cols["side"], cols["kind"],
            cols["price"], cols["volume"], symbols, cols["symbol_idx"],
            uuids, cols["uuid_idx"], cols["oids"],
        ))
    return frames

"""Gym-style vectorized market environment over the stacked books.

One `step` is one compiled call: inject the agent's ops into the first
`n_agent_ops` grid columns, generate a Hawkes/Zipf background grid for
the remaining columns (sim.flow), run the engine's batched step on the
`[S, ...]` book stack, and compute observations / reward / info from the
device-resident results — no host transfer anywhere in the transition,
so `rollout` can `lax.scan` thousands of steps on the accelerator
(JAX-LOB, arXiv:2308.13289 §4: the rollout loop must live on device or
RL throughput dies on the PCIe round trip).

Reward is mark-to-market PnL delta in float32 (cash + inventory * mid).
The matching arithmetic stays exact integer (engine envelope); the f32
here is diagnostic reward shaping only, never book state.

Capacity note: a jitted rollout cannot host-escalate geometry the way
`BatchEngine` does, so overflow is *reported* per step (`StepInfo.
book_overflow` / `fill_overflow`) instead of replayed; size `book.cap` /
`max_fills` for the flow (tests/test_sim.py asserts the counters stay
zero over a 1000-step rollout at cap=32 / K=8 with the default flow).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from typing import NamedTuple

from ..engine.batch import _batch_step_impl
from ..engine.book import BookConfig, BookState, DeviceOp, init_books
from .flow import FlowConfig, FlowState, flow_init, gen_ops


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    """Static environment parameters (hashable — jit static arg)."""

    flow: FlowConfig = FlowConfig()
    book: BookConfig = BookConfig(cap=16, max_fills=4, dtype=jnp.int32)
    n_agent_ops: int = 2  # agent op slots per step (grid columns 0..A-1)
    obs_levels: int = 4  # resting slots exposed per side in Obs
    agent_uid: int = 1 << 20  # above any background uid

    def __post_init__(self) -> None:
        if self.n_agent_ops <= 0:
            raise ValueError("sim env n_agent_ops must be positive")
        if not 0 < self.obs_levels <= self.book.cap:
            raise ValueError(
                f"sim env obs_levels must be in [1, cap], got "
                f"{self.obs_levels} (cap {self.book.cap})"
            )
        if self.agent_uid <= self.flow.n_uids:
            raise ValueError(
                "sim env agent_uid must exceed flow.n_uids (background "
                "uids would alias the agent's fills)"
            )


class AgentAction(NamedTuple):
    """The agent's op slots for one step — each leaf is `[A]`. `action`
    0 (NOP) makes a slot inert; ADD slots must carry volume >= 1 and the
    agent's own oid handles (disjoint from background oids, which count
    up from 1 — use e.g. oids >= 2**24). The env stamps `uid` itself."""

    lane: jax.Array  # i32 symbol lane
    action: jax.Array  # i32 0=NOP, 1=ADD, 2=DEL
    side: jax.Array  # i32 0=BUY, 1=SALE
    kind: jax.Array  # i32 types.OrderType number (0 LIMIT, 1 MARKET, ...)
    price: jax.Array  # book dtype ticks (absolute)
    volume: jax.Array  # book dtype lots
    oid: jax.Array  # book dtype order-id handle


class EnvState(NamedTuple):
    books: BookState  # [S, ...] stacked
    flow: FlowState
    t: jax.Array  # i32 step counter
    cash: jax.Array  # f32 signed cash (diagnostic units)
    inv: jax.Array  # i32 [S] net agent inventory (lots) per lane
    mtm: jax.Array  # f32 mark-to-market at the end of last step


class Obs(NamedTuple):
    """Device-side L1/L2 view of the books (the jit-compatible analogue
    of `engine.book.book_depth`). Depth slots are the top `L` *resting
    orders* per side in priority order (equal prices adjacent), masked to
    zero beyond `count` — aggregation to price levels is a host concern."""

    best_bid: jax.Array  # [S] book dtype (0 when side empty)
    best_ask: jax.Array  # [S]
    bid_prices: jax.Array  # [S, L]
    bid_lots: jax.Array  # [S, L]
    ask_prices: jax.Array  # [S, L]
    ask_lots: jax.Array  # [S, L]
    counts: jax.Array  # [S, 2] i32 resting orders per side
    mid: jax.Array  # [S] f32 (ref-banded fallback when a side is empty)
    lam: jax.Array  # [E] f32 current Hawkes intensities
    t: jax.Array  # i32 step counter


class StepInfo(NamedTuple):
    """Per-step diagnostics (all i32 scalars; sums wrap — `checksum` is
    the replay digest fold, not an exact count)."""

    events: jax.Array  # background + agent ops applied (action != 0)
    trades: jax.Array  # total fills (n_fills sum, incl. beyond-K)
    traded_qty: jax.Array  # lots traded (wrapping i32)
    fill_overflow: jax.Array  # fill records beyond K (0 = exact)
    book_overflow: jax.Array  # dropped resting inserts (0 = exact)
    cancels_missed: jax.Array  # DELs that found nothing
    agent_fills: jax.Array  # fills with the agent on either side
    checksum: jax.Array  # i32 [4] wrapping fold over the fill stream


def null_action(config: EnvConfig) -> AgentAction:
    """All-NOP agent action (background flow only)."""
    a = config.n_agent_ops
    dt = config.book.dtype
    z32 = jnp.zeros((a,), jnp.int32)
    zdt = jnp.zeros((a,), dt)
    return AgentAction(
        lane=z32, action=z32, side=z32, kind=z32,
        price=zdt, volume=zdt, oid=zdt,
    )


def _mid(config: EnvConfig, books: BookState):
    """[S] f32 mid price with the flow's reference band as fallback."""
    ref = float(config.flow.ref_price)
    half = float(config.flow.ref_spread)
    bb = jnp.where(
        books.count[:, 0] > 0, books.price[:, 0, 0].astype(jnp.float32),
        jnp.float32(ref - half),
    )
    ba = jnp.where(
        books.count[:, 1] > 0, books.price[:, 1, 0].astype(jnp.float32),
        jnp.float32(ref + half),
    )
    return 0.5 * (bb + ba)


def _observe(config: EnvConfig, books: BookState, flow: FlowState, t):
    ell = config.obs_levels
    dt = config.book.dtype
    slots = jnp.arange(ell, dtype=jnp.int32)
    live = slots[None, None, :] < books.count[:, :, None]  # [S, 2, L]
    prices = jnp.where(live, books.price[:, :, :ell], jnp.asarray(0, dt))
    lots = jnp.where(live, books.lots[:, :, :ell], jnp.asarray(0, dt))
    zero = jnp.asarray(0, dt)
    return Obs(
        best_bid=jnp.where(books.count[:, 0] > 0, books.price[:, 0, 0],
                           zero),
        best_ask=jnp.where(books.count[:, 1] > 0, books.price[:, 1, 0],
                           zero),
        bid_prices=prices[:, 0], bid_lots=lots[:, 0],
        ask_prices=prices[:, 1], ask_lots=lots[:, 1],
        counts=books.count,
        mid=_mid(config, books),
        lam=flow.lam,
        t=t,
    )


def _agent_grid(config: EnvConfig, act: AgentAction) -> DeviceOp:
    """Scatter the agent's [A] op slots into an [S, A] grid (slot a owns
    column a, so agent ops never collide and keep their order)."""
    s = config.flow.n_lanes
    a = config.n_agent_ops
    dt = config.book.dtype
    cols = jnp.arange(a, dtype=jnp.int32)
    on32 = (act.action != 0).astype(jnp.int32)
    ondt = on32.astype(dt)
    uid = jnp.asarray(config.agent_uid, dt) * ondt
    fields = {
        "action": (act.action * on32, jnp.int32),
        "side": (act.side * on32, jnp.int32),
        "kind": (act.kind * on32, jnp.int32),
        "price": (act.price * ondt, dt),
        "volume": (act.volume * ondt, dt),
        "oid": (act.oid * ondt, dt),
        "uid": (uid, dt),
    }
    return DeviceOp(**{
        f: jnp.zeros((s, a), d).at[act.lane, cols].set(v.astype(d))
        for f, (v, d) in fields.items()
    })


def _env_reset_impl(config: EnvConfig, key: jax.Array):
    books = init_books(config.book, config.flow.n_lanes)
    flow = flow_init(config.flow, key)
    t = jnp.zeros((), jnp.int32)
    state = EnvState(
        books=books, flow=flow, t=t,
        cash=jnp.zeros((), jnp.float32),
        inv=jnp.zeros((config.flow.n_lanes,), jnp.int32),
        mtm=jnp.zeros((), jnp.float32),
    )
    return state, _observe(config, books, flow, t)


def _env_step_impl(config: EnvConfig, state: EnvState, act: AgentAction):
    a = config.n_agent_ops
    flow2, bg_ops = gen_ops(config.flow, state.flow, state.books)
    ops = jax.tree.map(
        lambda x, y: jnp.concatenate([x, y], axis=1),
        _agent_grid(config, act), bg_ops,
    )
    books2, outs = _batch_step_impl(config.book, state.books, ops)

    # -- agent PnL (f32 cash, i32 per-lane inventory) ----------------------
    qty = outs.fill_qty.astype(jnp.float32)  # [S, T, K]
    price = outs.fill_price.astype(jnp.float32)
    agent_uid = jnp.asarray(config.agent_uid, config.book.dtype)
    filled = outs.fill_qty > 0
    # Maker side: taker's side is the op's side; the maker BUYS when the
    # taker sells (side == 1) and vice versa.
    maker = filled & (outs.maker_uid == agent_uid)
    taker_side = ops.side[:, :, None]
    mk_sign = jnp.where(taker_side == 1, 1.0, -1.0) * maker
    inv_maker = jnp.sum(
        outs.fill_qty * jnp.where(taker_side == 1, 1, -1) * maker,
        axis=(1, 2), dtype=jnp.int32,
    )  # [S]
    cash_maker = -jnp.sum(mk_sign * qty * price)
    # Taker side: the agent's own op slots live at known coordinates
    # (act.lane, column a) — sum their fill records directly.
    cols = jnp.arange(a, dtype=jnp.int32)
    t_qty = outs.fill_qty[act.lane, cols]  # [A, K]
    t_prc = price[act.lane, cols]
    t_sign = jnp.where(act.side == 0, 1, -1)[:, None]  # buy: +inv, -cash
    inv_taker = jnp.zeros_like(state.inv).at[act.lane].add(
        jnp.sum(t_qty * t_sign, axis=1, dtype=jnp.int32)
    )
    cash_taker = -jnp.sum(
        t_qty.astype(jnp.float32) * t_prc * t_sign.astype(jnp.float32)
    )
    inv2 = state.inv + inv_maker + inv_taker
    cash2 = state.cash + cash_maker + cash_taker
    agent_fills = jnp.sum(maker, dtype=jnp.int32) + jnp.sum(
        t_qty > 0, dtype=jnp.int32
    )

    t2 = state.t + 1
    obs = _observe(config, books2, flow2, t2)
    mtm2 = cash2 + jnp.sum(inv2.astype(jnp.float32) * obs.mid)
    reward = mtm2 - state.mtm

    q32 = outs.fill_qty.astype(jnp.int32)
    checksum = jnp.stack([
        jnp.sum(outs.n_fills, dtype=jnp.int32),
        jnp.sum(q32, dtype=jnp.int32),
        jnp.sum(q32 * outs.fill_price.astype(jnp.int32), dtype=jnp.int32),
        jnp.sum(q32 * outs.maker_oid.astype(jnp.int32), dtype=jnp.int32),
    ])
    info = StepInfo(
        events=jnp.sum(ops.action != 0, dtype=jnp.int32),
        trades=jnp.sum(outs.n_fills, dtype=jnp.int32),
        traded_qty=jnp.sum(q32, dtype=jnp.int32),
        fill_overflow=jnp.sum(outs.fill_overflow, dtype=jnp.int32),
        book_overflow=jnp.sum(outs.book_overflow, dtype=jnp.int32),
        cancels_missed=jnp.sum(
            (ops.action == 2) & (outs.cancel_found == 0), dtype=jnp.int32
        ),
        agent_fills=agent_fills,
        checksum=checksum,
    )
    state2 = EnvState(
        books=books2, flow=flow2, t=t2, cash=cash2, inv=inv2, mtm=mtm2
    )
    return state2, obs, reward, info


def _rollout_impl(config: EnvConfig, state: EnvState, n_steps: int):
    """Background-only rollout: `n_steps` env transitions in one
    `lax.scan` (the zero-host-transfer acceptance path). Returns the
    final state and the stacked per-step (reward, StepInfo) trajectory."""
    nop = null_action(config)

    def body(st, _):
        st2, _obs, reward, info = _env_step_impl(config, st, nop)
        return st2, (reward, info)

    final, traj = jax.lax.scan(body, state, None, length=n_steps)
    return final, traj


env_reset = functools.partial(jax.jit, static_argnums=0)(_env_reset_impl)
env_step = functools.partial(jax.jit, static_argnums=0)(_env_step_impl)
rollout = functools.partial(
    jax.jit, static_argnums=(0, 2)
)(_rollout_impl)


class MarketEnv:
    """Thin OO wrapper over the pure entries (reset/step/rollout) for
    callers that prefer holding the config once."""

    def __init__(self, config: EnvConfig | None = None):
        self.config = config if config is not None else EnvConfig()

    def reset(self, key):
        return env_reset(self.config, key)

    def step(self, state, action):
        return env_step(self.config, state, action)

    def null_action(self):
        return null_action(self.config)

    def rollout(self, state, n_steps: int):
        return rollout(self.config, state, int(n_steps))

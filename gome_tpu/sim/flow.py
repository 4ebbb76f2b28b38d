"""On-device Hawkes/Zipf order-flow generator.

Model (arXiv:2510.08085 §2, discretized): six event types — {submit,
cancel, market} x {buy, sell} — share a multivariate Hawkes intensity
vector lambda[E] with exponential kernels:

    lambda_i(t) = mu_i + sum_j sum_{t_k^j < t} alpha[i][j] exp(-beta (t - t_k^j))

Each generated grid discretizes `t_bins` micro-bins of width `dt`; per
bin at most one event occurs (Bernoulli thinning with p = 1 - exp(-Lambda
dt)), its type is categorical in lambda, its symbol lane is Zipf(a)-
categorical (JAX-LOB's symbol-popularity model), and the intensity vector
decays + self/cross-excites per bin inside a `lax.scan`. Stationarity
requires the branching matrix alpha/beta to have spectral radius < 1
(:meth:`FlowConfig.branching_ratio`).

Placement: limit orders price at a geometric offset from the *opposite*
best quote (offset 0 = a marketable limit at the touch; larger offsets
rest deeper), falling back to a reference band when the book side is
empty. Cancels target a uniformly random resting slot of the lane's book
(gathered oid + exact resting price, the DEL contract of engine/step.py);
an empty side yields a deliberate miss (oid 0 is never assigned).

Everything here runs inside jit on device values — the emitted grid is a
`DeviceOp` in exactly the `[S, T]` layout `engine.batch` consumes (int32
for `GRID_I32_FIELDS`, book dtype elsewhere), so a generated frame feeds
`_batch_step_impl` with zero host round-trips (GL5xx) and the intensity
state never leaves the accelerator.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from typing import NamedTuple

from ..engine.book import GRID_I32_FIELDS, BookState, DeviceOp

# Event-type index = kind * 2 + side (kind: 0 submit, 1 cancel, 2 market;
# side: 0 BUY, 1 SALE) — so `etype % 2` is the side and `etype // 2` the
# kind, branch-free.
EV_SUBMIT_BUY = 0
EV_SUBMIT_SALE = 1
EV_CANCEL_BUY = 2
EV_CANCEL_SALE = 3
EV_MARKET_BUY = 4
EV_MARKET_SALE = 5
N_EVENT_TYPES = 6


@dataclasses.dataclass(frozen=True)
class FlowConfig:
    """Static flow-generator parameters (hashable — jit static arg).

    Intensities are per model-time unit; `dt` is the micro-bin width, so
    the per-bin event probability is 1 - exp(-sum(mu-ish) * dt) and one
    generated grid spans `t_bins * dt` model time. The excitation matrix
    is structured: an event adds `excite_self` to its own type's
    intensity, `excite_cross` to the same kind's opposite side, and
    `excite_kind` to every other type (all scaled by `decay` so the
    *branching* contribution alpha/beta is exactly those numbers — row
    sums must stay < 1)."""

    n_lanes: int = 256
    t_bins: int = 32
    dt: float = 0.02
    # Base intensities per kind (split evenly across the two sides).
    submit_rate: float = 2.0
    cancel_rate: float = 1.4
    market_rate: float = 0.6
    # Branching fractions (alpha = these * decay).
    excite_self: float = 0.25
    excite_cross: float = 0.10
    excite_kind: float = 0.05
    decay: float = 2.0
    zipf_a: float = 1.1
    # Geometric placement offset from the opposite touch (p = offset_p;
    # offset 0 = marketable limit) clamped to max_offset ticks.
    offset_p: float = 0.35
    max_offset: int = 200
    ref_price: int = 100_000  # empty-book fallback mid (ticks)
    ref_spread: int = 20  # fallback half-spread (ticks)
    vol_max: int = 100  # volumes uniform in [1, vol_max] lots
    n_uids: int = 256  # background uids in [1, n_uids]

    def __post_init__(self) -> None:
        if self.n_lanes <= 0 or self.t_bins <= 0:
            raise ValueError("sim flow n_lanes/t_bins must be positive")
        if self.dt <= 0 or self.decay <= 0:
            raise ValueError("sim flow dt/decay must be positive")
        if not (self.submit_rate > 0 or self.cancel_rate > 0
                or self.market_rate > 0):
            raise ValueError("sim flow needs a positive base rate")
        if self.vol_max <= 0 or self.n_uids <= 0:
            raise ValueError("sim flow vol_max/n_uids must be positive")
        if not 0 < self.offset_p < 1:
            raise ValueError(
                f"sim flow offset_p must be in (0, 1), got {self.offset_p}"
            )
        if self.ref_price <= self.ref_spread:
            raise ValueError("sim flow ref_price must exceed ref_spread")
        br = self.branching_ratio()
        if br >= 1.0:
            raise ValueError(
                f"sim flow Hawkes process is unstable: branching ratio "
                f"{br:.3f} >= 1 (lower excite_* or raise decay)"
            )
        # Thinning validity: with <= 1 event per bin, the discretization
        # saturates when the stationary rate mu_total / (1 - n) fills a
        # bin with high probability — the Bernoulli cap then clips the
        # excitation (the realized process stops being Hawkes: branching
        # collapses and counts go UNDERdispersed).
        rate = float(self.mu().sum()) / (1.0 - br)
        p_bin = 1.0 - math.exp(-rate * self.dt)
        if p_bin > 0.6:
            raise ValueError(
                f"sim flow dt too coarse: stationary per-bin event "
                f"probability {p_bin:.2f} > 0.6 saturates the one-event-"
                f"per-bin thinning (lower dt or the base rates)"
            )

    # -- derived model parameters (host-side, static) ---------------------
    def mu(self) -> np.ndarray:
        """Base intensity per event type [E] (kind rate split per side)."""
        per_side = [self.submit_rate / 2, self.cancel_rate / 2,
                    self.market_rate / 2]
        return np.repeat(np.asarray(per_side, np.float64), 2)

    def alpha(self) -> np.ndarray:
        """Excitation jump matrix [E, E]: event of type j adds
        alpha[i, j] to intensity i."""
        a = np.full((N_EVENT_TYPES, N_EVENT_TYPES),
                    self.excite_kind, np.float64)
        for j in range(N_EVENT_TYPES):
            a[j, j] = self.excite_self
            a[j ^ 1, j] = self.excite_cross  # same kind, opposite side
        return a * self.decay

    def branching_ratio(self) -> float:
        """Spectral radius of the branching matrix alpha/beta — the
        Hawkes stability bound (< 1 <=> stationary; arXiv:2510.08085
        eq. 4). With the structured alpha the all-ones vector is the
        Perron eigenvector, but compute it generally."""
        m = self.alpha() / self.decay
        return float(np.max(np.abs(np.linalg.eigvals(m))))


class FlowState(NamedTuple):
    """Device-resident generator state (a scan carry)."""

    lam: jax.Array  # f32 [E] current Hawkes intensities
    key: jax.Array  # PRNG key
    next_oid: jax.Array  # i32 next order-id handle (oid 0 never assigned)
    t_model: jax.Array  # f32 elapsed model time (diagnostics)


def flow_init(config: FlowConfig, key: jax.Array) -> FlowState:
    """Fresh generator state at the base intensity."""
    return FlowState(
        lam=jnp.asarray(config.mu(), jnp.float32),
        key=key,
        next_oid=jnp.ones((), jnp.int32),
        t_model=jnp.zeros((), jnp.float32),
    )


def _zipf_logits(config: FlowConfig) -> jax.Array:
    """Static log-weights for Zipf(a) symbol popularity over ranks
    1..n_lanes (lane 0 is the hottest symbol)."""
    ranks = np.arange(1, config.n_lanes + 1, dtype=np.float64)
    return jnp.asarray(-config.zipf_a * np.log(ranks), jnp.float32)


def _bin_events(config: FlowConfig, lam, key, oid0):
    """Inner per-bin scan: thinned Hawkes event stream for one grid.

    Returns the carry (lam, key, next_oid) and per-bin arrays [T]:
    occur (i32 0/1), etype, lane, uid, oid, vol (i32) and u_price,
    u_cancel (f32 placement draws, resolved against books afterwards)."""
    # All scalar model constants are pinned f32 up front: a bare python
    # float closed over by the scan body would enter the jaxpr as a
    # weak-typed float64 constant under x64 (GL201 in the envelope audit).
    decay = jnp.float32(math.exp(-config.decay * config.dt))
    mu = jnp.asarray(config.mu(), jnp.float32)
    alpha = jnp.asarray(config.alpha(), jnp.float32)
    zipf = _zipf_logits(config)
    dt = jnp.float32(config.dt)
    one = jnp.float32(1.0)
    eps = jnp.float32(1e-12)
    zero = jnp.float32(0.0)

    def body(carry, _):
        lam, key, oid = carry
        key, k_ev, k_ty, k_ln, k_pr, k_cx, k_vol, k_uid = jax.random.split(
            key, 8
        )
        lam_total = jnp.sum(lam)
        p_event = one - jnp.exp(-lam_total * dt)
        occur = (
            jax.random.uniform(k_ev, (), jnp.float32) < p_event
        ).astype(jnp.int32)
        etype = jax.random.categorical(
            k_ty, jnp.log(lam + eps)
        ).astype(jnp.int32)
        lane = jax.random.categorical(k_ln, zipf).astype(jnp.int32)
        u_price = jax.random.uniform(k_pr, (), jnp.float32)
        u_cancel = jax.random.uniform(k_cx, (), jnp.float32)
        vol = jax.random.randint(
            k_vol, (), 1, config.vol_max + 1, jnp.int32
        )
        uid = jax.random.randint(
            k_uid, (), 1, config.n_uids + 1, jnp.int32
        )
        is_add = occur * (1 - (etype // 2 == 1).astype(jnp.int32))
        oid_here = oid  # assigned only when this bin emits an ADD
        oid = oid + is_add
        lam = mu + (lam - mu) * decay + jnp.where(
            occur > 0, alpha[:, etype], zero
        )
        out = (occur, etype, lane, uid, oid_here, vol, u_price, u_cancel)
        return (lam, key, oid), out

    carry, outs = jax.lax.scan(
        body, (lam, key, oid0), None, length=config.t_bins
    )
    return carry, outs


def gen_ops(
    config: FlowConfig, state: FlowState, books: BookState
) -> tuple[FlowState, DeviceOp]:
    """One grid of background flow: `(state, books) -> (state', ops)`.

    `books` is the frame-start `[S, ...]` stacked BookState the placement
    model quotes against (best bid/ask per lane; cancel targets gathered
    from resting slots) — the caller applies the returned `[S, T]` grid
    to those books afterwards (engine.batch semantics: each bin owns one
    grid column, so bin order is arrival order and cells never collide).
    Pure jit-traceable; all shapes static in `config`."""
    s_lanes, t_bins = config.n_lanes, config.t_bins
    dtype = books.price.dtype
    (lam, key, next_oid), outs = _bin_events(
        config, state.lam, state.key, state.next_oid
    )
    occur, etype, lane, uid, oid_new, vol, u_price, u_cancel = outs

    kind = etype // 2  # 0 submit, 1 cancel, 2 market
    side = (etype % 2).astype(jnp.int32)
    is_cancel = (kind == 1).astype(jnp.int32)
    is_market = (kind == 2).astype(jnp.int32)

    # -- placement against the frame-start books ([T] gathers) ------------
    ref_mid = jnp.asarray(config.ref_price, dtype)
    ref_half = jnp.asarray(config.ref_spread, dtype)
    cnt = books.count[lane]  # [T, 2] i32
    best_bid = jnp.where(
        cnt[:, 0] > 0, books.price[lane, 0, 0], ref_mid - ref_half
    )
    best_ask = jnp.where(
        cnt[:, 1] > 0, books.price[lane, 1, 0], ref_mid + ref_half
    )
    # Geometric offset from the opposite touch: k = floor(log(1-u) /
    # log(1-p)) in {0, 1, ...}; k = 0 is a marketable limit.
    k_off = jnp.floor(
        jnp.log1p(-u_price * jnp.float32(1.0 - 1e-7))
        * jnp.float32(1.0 / math.log(1.0 - config.offset_p))
    ).astype(jnp.int32)
    k_off = jnp.minimum(k_off, jnp.int32(config.max_offset)).astype(dtype)
    limit_price = jnp.where(side == 0, best_ask - k_off, best_bid + k_off)
    limit_price = jnp.maximum(limit_price, jnp.asarray(1, dtype))

    # -- cancel targeting: uniform resting slot of the lane's side --------
    n_side = jnp.take_along_axis(cnt, side[:, None], axis=1)[:, 0]  # [T]
    slot = jnp.minimum(
        (u_cancel * n_side.astype(jnp.float32)).astype(jnp.int32),
        jnp.maximum(n_side - 1, 0),
    )
    c_oid = books.oid[lane, side, slot]
    c_price = books.price[lane, side, slot]
    c_uid = books.uid[lane, side, slot]
    hit = (n_side > 0).astype(jnp.int32)
    # Miss: oid 0 / price 0 never matches a resting order (oids start at
    # 1, prices at 1) — the engine reports cancel_found=0, same as the
    # oracle's not-found path.
    c_oid = jnp.where(hit > 0, c_oid, jnp.asarray(0, dtype))
    c_price = jnp.where(hit > 0, c_price, jnp.asarray(0, dtype))

    # -- field resolution per bin ([T], then scattered to [S, T]) ---------
    action = occur * jnp.where(is_cancel > 0, 2, 1)
    price = jnp.where(
        is_cancel > 0, c_price,
        jnp.where(is_market > 0, jnp.asarray(0, dtype), limit_price),
    )
    oid = jnp.where(is_cancel > 0, c_oid, oid_new.astype(dtype))
    volume = jnp.where(
        is_cancel > 0, jnp.asarray(0, dtype), vol.astype(dtype)
    )
    # A hitting cancel is issued by the resting order's OWNER (uid is
    # reporting-only for matching, but the service pre-pool keys on
    # symbol:uuid:oid — a random uid there would always miss).
    uid = jnp.where(
        (is_cancel > 0) & (hit > 0), c_uid.astype(jnp.int32), uid
    )

    mask_i32 = occur
    mask_dt = occur.astype(dtype)
    cols = {
        "action": action,
        "side": side * mask_i32,
        "kind": is_market * mask_i32,  # LIMIT 0 / MARKET 1
        "price": price * mask_dt,
        "volume": volume * mask_dt,
        "oid": oid * mask_dt,
        "uid": uid.astype(dtype) * mask_dt,
    }
    tt = jnp.arange(t_bins, dtype=jnp.int32)

    def scat(vals, dt_):
        return jnp.zeros((s_lanes, t_bins), dt_).at[lane, tt].set(
            vals.astype(dt_)
        )

    ops = DeviceOp(**{
        f: scat(cols[f], jnp.int32 if f in GRID_I32_FIELDS else dtype)
        for f in DeviceOp._fields
    })
    new_state = FlowState(
        lam=lam,
        key=key,
        next_oid=next_oid,
        t_model=state.t_model + jnp.float32(t_bins * config.dt),
    )
    return new_state, ops


#: Standalone compiled entry (the env inlines gen_ops into its own step).
gen_ops_jit = functools.partial(jax.jit, static_argnums=0)(gen_ops)

"""Empirical diagnostics for the flow generator (host-side numpy).

The generator claims three statistical properties; each has an estimator
here so tests can assert them on seeded samples instead of trusting the
implementation (arXiv:2510.08085 §4 validates its simulator the same
way):

  * symbol popularity is Zipf(a)      -> `zipf_exponent` (log-log fit)
  * the Hawkes process is subcritical -> `empirical_branching_ratio` vs
    `FlowConfig.branching_ratio` (the configured spectral bound)
  * event times cluster (self-excitation) -> `dispersion_index` > 1
    where a Poisson stream of the same rate gives ~1

`sample_grids` provides the seeded sample: N generated grids' (action,
side, kind) layers, one device fetch at the end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.book import BookConfig, init_books
from .flow import FlowConfig, flow_init, gen_ops


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _sample_grids_impl(
    config: FlowConfig, book_config: BookConfig, key, n_grids: int
):
    """Stack n_grids of generated (action, side, kind) layers
    [N, S, T] against a fixed empty book stack (pricing falls back to
    the reference band; cancels all miss — occurrence, type, and lane
    statistics do not depend on book state)."""
    books = init_books(book_config, config.n_lanes)
    state = flow_init(config, key)

    def body(st, _):
        st2, ops = gen_ops(config, st, books)
        return st2, (ops.action, ops.side, ops.kind)

    _, layers = jax.lax.scan(body, state, None, length=n_grids)
    return layers


def sample_grids(
    config: FlowConfig, seed: int, n_grids: int,
    book_config: BookConfig | None = None,
) -> dict:
    """Seeded sample as host numpy: {"action", "side", "kind"},
    each [N, S, T] int32."""
    if book_config is None:
        book_config = BookConfig(cap=4, max_fills=1, dtype=jnp.int32)
    action, side, kind = jax.device_get(_sample_grids_impl(
        config, book_config, jax.random.PRNGKey(seed), n_grids
    ))
    return {
        "action": np.asarray(action),
        "side": np.asarray(side),
        "kind": np.asarray(kind),
    }


def symbol_counts(sample: dict) -> np.ndarray:
    """Events per lane [S], summed over grids and bins."""
    return (sample["action"] != 0).sum(axis=(0, 2))


def zipf_exponent(counts: np.ndarray) -> float:
    """Least-squares slope of log(frequency) vs log(rank) over the lanes
    that fired — recovers `a` when counts follow rank^(-a). Lane order IS
    rank order (flow._zipf_logits assigns lane 0 the heaviest weight)."""
    counts = np.asarray(counts, np.float64)
    ranks = np.arange(1, len(counts) + 1, dtype=np.float64)
    live = counts > 0
    if live.sum() < 2:
        raise ValueError("need events on >= 2 lanes to fit an exponent")
    x = np.log(ranks[live])
    y = np.log(counts[live])
    slope = np.polyfit(x, y, 1)[0]
    return float(-slope)


def events_per_grid(sample: dict) -> np.ndarray:
    """Event count per generated grid [N] (the bin-aggregated counting
    process the clustering/branching estimators run on)."""
    return (sample["action"] != 0).sum(axis=(1, 2))


def dispersion_index(counts_per_window: np.ndarray) -> float:
    """Index of dispersion var/mean of window counts: ~1 for Poisson,
    > 1 for a clustered (self-exciting) stream."""
    c = np.asarray(counts_per_window, np.float64)
    mean = c.mean()
    if mean == 0:
        raise ValueError("no events in sample")
    return float(c.var(ddof=1) / mean)


def empirical_branching_ratio(
    config: FlowConfig, n_events: int, n_grids: int
) -> float:
    """Moment estimator n_hat = 1 - mu_total * T / N (stationary Hawkes:
    the event rate is mu_total / (1 - n) with n the branching ratio —
    arXiv:2510.08085 eq. 6). `T` is total model time spanned; thinning
    discretization (<= 1 event/bin) biases it slightly low at high
    per-bin occupancy, so tests compare with a tolerance."""
    if n_events <= 0:
        raise ValueError("no events in sample")
    total_time = n_grids * config.t_bins * config.dt
    mu_total = float(config.mu().sum())
    return 1.0 - mu_total * total_time / n_events

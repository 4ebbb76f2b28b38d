"""gome_tpu.sim: flow-generator contract, env semantics, statistical
validation, zero-transfer rollout (the acceptance sweep), and seeded
bit-exact replay across processes."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.engine.book import GRID_I32_FIELDS, BookConfig, DeviceOp, init_books
from gome_tpu.sim import (
    AgentAction,
    EnvConfig,
    FlowConfig,
    MarketEnv,
    env_reset,
    env_step,
    flow_init,
    gen_ops_jit,
    make_manifest,
    null_action,
    record_frames,
    rollout,
    run_from_manifest,
)
from gome_tpu.sim import stats as sim_stats
from gome_tpu.sim.replay import env_config_from_manifest

# A quiet flow for agent-scenario tests: rates so low that background
# events are (astronomically) improbable over a few steps, leaving the
# books entirely to the agent. Rates must be positive by contract.
QUIET = FlowConfig(
    n_lanes=4, t_bins=8, submit_rate=1e-8, cancel_rate=1e-8,
    market_rate=1e-8,
)


def small_env(n_lanes=8, **kw):
    return EnvConfig(
        flow=FlowConfig(n_lanes=n_lanes, t_bins=16),
        book=BookConfig(cap=16, max_fills=4, dtype=jnp.int32),
        **kw,
    )


# -- flow: grid contract ------------------------------------------------------

class TestFlowGrid:
    def test_grid_layout_and_dtypes(self):
        config = FlowConfig(n_lanes=8, t_bins=32)
        books = init_books(BookConfig(cap=8, max_fills=2, dtype=jnp.int32), 8)
        state = flow_init(config, jax.random.PRNGKey(0))
        state2, ops = gen_ops_jit(config, state, books)
        assert isinstance(ops, DeviceOp)
        for f in DeviceOp._fields:
            leaf = getattr(ops, f)
            assert leaf.shape == (8, 32), f
            want = jnp.int32  # book dtype is int32 here too
            assert leaf.dtype == want, f
        host = jax.device_get(ops)
        assert set(np.unique(host.action)) <= {0, 1, 2}
        # Each bin owns one grid column: at most one event per column.
        assert ((host.action != 0).sum(axis=0) <= 1).all()
        occupied = host.action != 0
        # NOP cells are fully zeroed (inert anywhere in the grid).
        for f in DeviceOp._fields:
            assert (getattr(host, f)[~occupied] == 0).all(), f
        # DELs carry volume 0; markets price 0; ADD prices >= 1.
        adds = host.action == 1
        dels = host.action == 2
        assert (host.volume[dels] == 0).all()
        assert (host.volume[adds] >= 1).all()
        mkts = host.kind == 1
        assert (host.price[mkts & adds] == 0).all()
        assert (host.price[adds & ~mkts] >= 1).all()
        # The intensity state advanced.
        assert int(state2.next_oid) >= 1
        assert float(state2.t_model) > 0

    def test_grid_i64_book_dtype(self):
        config = FlowConfig(n_lanes=4, t_bins=8)
        books = init_books(BookConfig(cap=8, max_fills=2, dtype=jnp.int64), 4)
        state = flow_init(config, jax.random.PRNGKey(1))
        _, ops = gen_ops_jit(config, state, books)
        for f in DeviceOp._fields:
            want = jnp.int32 if f in GRID_I32_FIELDS else jnp.int64
            assert getattr(ops, f).dtype == want, f

    def test_deterministic_in_key(self):
        config = FlowConfig(n_lanes=8, t_bins=32)
        books = init_books(BookConfig(cap=8, max_fills=2, dtype=jnp.int32), 8)

        def run():
            state = flow_init(config, jax.random.PRNGKey(7))
            _, ops = gen_ops_jit(config, state, books)
            return jax.device_get(ops)

        a, b = run(), run()
        for f in DeviceOp._fields:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))

    def test_unstable_hawkes_raises(self):
        with pytest.raises(ValueError, match="unstable"):
            FlowConfig(excite_self=0.9, excite_cross=0.2)

    def test_saturated_discretization_raises(self):
        with pytest.raises(ValueError, match="saturates"):
            FlowConfig(dt=0.5)


# -- flow: statistical validation ---------------------------------------------

class TestFlowStats:
    @pytest.fixture(scope="class")
    def sample(self):
        config = FlowConfig(n_lanes=32, t_bins=64)
        return config, sim_stats.sample_grids(config, 0, 300)

    def test_zipf_exponent(self, sample):
        config, s = sample
        fit = sim_stats.zipf_exponent(sim_stats.symbol_counts(s))
        assert abs(fit - config.zipf_a) < 0.3, fit

    def test_hawkes_branching_and_clustering(self, sample):
        config, s = sample
        per_grid = sim_stats.events_per_grid(s)
        n_hat = sim_stats.empirical_branching_ratio(
            config, int(per_grid.sum()), len(per_grid)
        )
        # Thinning discretization biases the estimate low; it must still
        # sit well above zero and below the configured spectral bound.
        assert 0.25 < n_hat < config.branching_ratio() + 0.05, n_hat
        # Self-excitation clusters events: overdispersed window counts.
        assert sim_stats.dispersion_index(per_grid) > 1.2

    def test_poisson_limit(self):
        # Near-zero excitation: a Poisson stream — dispersion ~ 1 and
        # branching estimate ~ 0.
        config = FlowConfig(
            n_lanes=32, t_bins=64, excite_self=1e-6, excite_cross=1e-6,
            excite_kind=1e-6,
        )
        s = sim_stats.sample_grids(config, 1, 300)
        per_grid = sim_stats.events_per_grid(s)
        assert abs(sim_stats.dispersion_index(per_grid) - 1.0) < 0.25
        n_hat = sim_stats.empirical_branching_ratio(
            config, int(per_grid.sum()), len(per_grid)
        )
        assert abs(n_hat) < 0.12, n_hat


# -- env: reset/step/rollout --------------------------------------------------

class TestEnv:
    def test_reset_step_shapes(self):
        config = small_env()
        s, e, ell = 8, 6, config.obs_levels
        state, obs = env_reset(config, jax.random.PRNGKey(0))
        assert obs.best_bid.shape == (s,)
        assert obs.bid_prices.shape == (s, ell)
        assert obs.counts.shape == (s, 2) and obs.counts.dtype == jnp.int32
        assert obs.mid.shape == (s,) and obs.mid.dtype == jnp.float32
        assert obs.lam.shape == (e,) and obs.lam.dtype == jnp.float32
        state2, obs2, reward, info = env_step(
            config, state, null_action(config)
        )
        assert reward.shape == () and reward.dtype == jnp.float32
        assert info.trades.dtype == jnp.int32
        assert info.checksum.shape == (4,)
        assert int(state2.t) == 1
        assert state2.inv.shape == (s,)

    def test_rollout_scan_trajectory(self):
        config = small_env()
        state, _ = env_reset(config, jax.random.PRNGKey(2))
        final, (rewards, info) = rollout(config, state, 20)
        assert rewards.shape == (20,)
        assert info.events.shape == (20,)
        assert int(final.t) == 20
        assert int(jax.device_get(info.events).sum()) > 0

    def test_market_env_wrapper(self):
        env = MarketEnv(small_env())
        state, obs = env.reset(jax.random.PRNGKey(0))
        state, obs, reward, info = env.step(state, env.null_action())
        assert int(state.t) == 1

    def test_agent_maker_taker_pnl(self):
        # Background silenced: the agent trades against itself on lane 1
        # — rest a bid, lift it with a market sale, then cancel the rest.
        config = EnvConfig(
            flow=QUIET,
            book=BookConfig(cap=8, max_fills=4, dtype=jnp.int32),
            n_agent_ops=2,
        )
        state, obs = env_reset(config, jax.random.PRNGKey(0))
        z = np.zeros(2, np.int32)
        oid = 1 << 24  # agent handles live above background oids

        def act(**kw):
            base = dict(
                lane=z, action=z, side=z, kind=z, price=z,
                volume=z, oid=z,
            )
            base.update({
                k: np.asarray(v, np.int32) for k, v in kw.items()
            })
            return AgentAction(**base)

        # Step 1: slot 0 rests BUY 5 @ 100 on lane 1.
        state, obs, reward, info = env_step(config, state, act(
            lane=[1, 0], action=[1, 0], side=[0, 0], price=[100, 0],
            volume=[5, 0], oid=[oid, 0],
        ))
        assert int(obs.best_bid[1]) == 100
        assert int(obs.counts[1, 0]) == 1
        assert int(info.trades) == 0
        # Step 2: slot 0 market-SELLs 2 into the resting bid.
        state, obs, reward, info = env_step(config, state, act(
            lane=[1, 0], action=[1, 0], side=[1, 0], kind=[1, 0],
            volume=[2, 0], oid=[oid + 1, 0],
        ))
        assert int(info.trades) == 1
        assert int(info.traded_qty) == 2
        assert int(info.agent_fills) == 2  # maker AND taker records
        host = jax.device_get(state)
        # Self-trade: maker +2, taker -2 inventory; cash nets to zero.
        assert int(host.inv[1]) == 0
        assert float(host.cash) == pytest.approx(0.0)
        assert int(obs.bid_lots[1, 0]) == 3  # 5 rested - 2 filled
        # Step 3: slot 0 cancels the remainder (exact resting price).
        state, obs, reward, info = env_step(config, state, act(
            lane=[1, 0], action=[2, 0], side=[0, 0], price=[100, 0],
            oid=[oid, 0],
        ))
        assert int(info.cancels_missed) == 0
        assert int(obs.counts[1, 0]) == 0

    def test_env_config_validation(self):
        with pytest.raises(ValueError, match="agent_uid"):
            EnvConfig(flow=FlowConfig(n_lanes=4), agent_uid=8)
        with pytest.raises(ValueError, match="obs_levels"):
            EnvConfig(
                book=BookConfig(cap=4, max_fills=2, dtype=jnp.int32),
                obs_levels=9,
            )


# -- acceptance: zero-transfer 1000-step rollout over 256 books ---------------

class TestZeroTransferRollout:
    CONFIG = EnvConfig(
        flow=FlowConfig(n_lanes=256),
        book=BookConfig(cap=32, max_fills=8, dtype=jnp.int32),
    )

    def test_rollout_1000_steps_no_host_transfers(self):
        config = self.CONFIG
        state0, _ = env_reset(config, jax.random.PRNGKey(3))
        # Warm the compile off the guard, on throwaway state.
        _ = rollout(config, state0, 1000)
        state, _ = env_reset(config, jax.random.PRNGKey(3))
        # Runtime assertion: the whole 1000-step scan must execute with
        # zero host<->device transfers (the GL5xx contract, enforced by
        # the runtime, not just static analysis).
        with jax.transfer_guard("disallow"):
            final, (rewards, info) = rollout(config, state, 1000)
        jax.block_until_ready(info.checksum)
        ev, tr, b_over, f_over = jax.device_get(
            (info.events, info.trades, info.book_overflow,
             info.fill_overflow)
        )
        assert ev.shape == (1000,)
        assert int(ev.sum()) > 1000  # flow actually ran
        assert int(tr.sum()) > 100  # and actually traded
        # Exactness: geometry absorbs the whole flow (no silent drops).
        assert int(b_over.sum()) == 0
        assert int(f_over.sum()) == 0

    def test_rollout_jaxpr_has_no_callbacks(self):
        config = self.CONFIG
        state, _ = env_reset(config, jax.random.PRNGKey(0))
        txt = str(jax.make_jaxpr(
            lambda st: rollout(config, st, 8)
        )(state))
        for prim in ("callback", "outside_call", "infeed", "outfeed"):
            assert prim not in txt, prim


# -- replay: manifests, two-process bit-exactness, GCO record mode ------------

REPLAY_CONFIG = EnvConfig(
    flow=FlowConfig(n_lanes=16, t_bins=32),
    book=BookConfig(cap=16, max_fills=4, dtype=jnp.int32),
)

_REPLAY_CHILD = """
import json, sys
import jax
jax.config.update("jax_enable_x64", True)
from gome_tpu.sim import run_from_manifest
print(json.dumps(run_from_manifest(json.load(open(sys.argv[1])))))
"""


class TestReplay:
    def test_manifest_roundtrip(self):
        m = make_manifest(REPLAY_CONFIG, seed=9, n_steps=12)
        blob = json.loads(json.dumps(m))  # survive serialization
        assert env_config_from_manifest(blob) == REPLAY_CONFIG

    def test_manifest_hash_mismatch_raises(self):
        m = make_manifest(REPLAY_CONFIG, seed=9, n_steps=12)
        m = json.loads(json.dumps(m))
        m["config"]["flow"]["zipf_a"] = 1.3  # hand-edited
        with pytest.raises(ValueError, match="hash mismatch"):
            env_config_from_manifest(m)
        m2 = make_manifest(REPLAY_CONFIG, seed=9, n_steps=12)
        m2["version"] = 99
        with pytest.raises(ValueError, match="version"):
            env_config_from_manifest(m2)

    def test_two_process_bit_exact_replay(self, tmp_path):
        manifest = make_manifest(REPLAY_CONFIG, seed=41, n_steps=40)
        here = run_from_manifest(manifest)
        assert here["events"] > 0
        # Same manifest, fresh interpreter: the digest covers every fill
        # record and every final book leaf, so equality is bit-exactness
        # of the whole trade sequence and book evolution.
        path = tmp_path / "manifest.json"
        path.write_text(json.dumps(manifest))
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            )),
        )
        out = subprocess.run(
            [sys.executable, "-c", _REPLAY_CHILD, str(path)],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        there = json.loads(out.stdout.strip().splitlines()[-1])
        assert there == here

    def test_in_process_replay_deterministic(self):
        manifest = make_manifest(REPLAY_CONFIG, seed=5, n_steps=25)
        assert run_from_manifest(manifest) == run_from_manifest(manifest)
        other = run_from_manifest(
            make_manifest(REPLAY_CONFIG, seed=6, n_steps=25)
        )
        assert other["digest"] != run_from_manifest(manifest)["digest"]

    def test_record_frames_feed_service_codec(self):
        from gome_tpu.bus.colwire import decode_order_frame
        from gome_tpu.engine.frames import orders_from_frame
        from gome_tpu.engine.orchestrator import MatchEngine

        config = EnvConfig(
            flow=FlowConfig(n_lanes=8, t_bins=32),
            book=BookConfig(cap=16, max_fills=4, dtype=jnp.int32),
        )
        frames = record_frames(config, seed=2, n_steps=10)
        assert frames, "flow produced no frames in 10 steps"
        engine = MatchEngine(
            config=BookConfig(cap=32, max_fills=8, dtype=jnp.int32),
            n_slots=8, max_t=16,
        )
        n_orders = n_events = 0
        for payload in frames:
            cols = decode_order_frame(payload)
            orders = orders_from_frame(cols)
            n_orders += len(orders)
            n_events += len(engine.process(orders))
        assert n_orders > 0
        engine.batch.verify_books()

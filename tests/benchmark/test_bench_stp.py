"""`spot10k_stp` and its cell `spot10k_stp.sat` (PR 41): spot10k's venue under
self-trade prevention, rule expire_taker, with 16 users. The configuration is
spot10k's but for the keys that state the rule; its plain reference imports
nothing of the program and agrees with the program's own oracle under the rule
event for event, on every kind; on the venue's stream no event has one uid on
both sides, adds stop at their owner's order in every seed, and the plain Book
is another venue; the cell's rehearsal is `correct`, its control is not, and
the same stream against a service without the rule is not."""

import json
import os
import random
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import compare, reference, spec, stream

ROOT = spec.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
CELL = "spot10k_stp.sat"
R = 128
PER_LAYER = [
    "outstanding_mean.sat", "admit_us_per_order.sat",
    "order_backlog_frames.sat", "feed_ms_per_frame.sat",
    "device_calls_per_frame.sat", "rewinds_in_window.sat",
    "kernel_us_per_op.sat", "match_kernel_roofline.sat",
    "device_idle_share.sat", "publish_ms_per_frame.sat",
    "fanout_us_per_event.sat", "events_per_order.sat",
]


def config_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def rehearsal_flow():
    config = config_of("spot10k_stp")
    spec._merge(config, config["rehearsal"])
    return config["flow"], os.path.join(ROOT, config["reference"])


def test_the_configuration_is_spot10ks_but_for_the_keys_that_state_the_rule():
    stp, one = config_of("spot10k_stp"), config_of("spot10k")
    assert stp["service"]["engine"].pop("self_trade") == "expire_taker"
    assert stp["flow"].pop("users") == 16 and one["flow"].pop("users") == 256
    differ = sorted(k for k in set(stp) | set(one) if stp.get(k) != one.get(k))
    assert differ == ["assumed", "guarantees", "name", "reference", "source"]
    assert stp["name"] == "spot10k_stp" and stp["reduced"] == []
    assert stp["reference"] == "benchmark/configs/spot10k_stp_reference.py"
    assert stp["guarantees"][:5] == one["guarantees"]
    assert len(stp["guarantees"]) == 7
    assert "same uid on both sides" in stp["guarantees"][5]
    assert "fill-or-kill" in stp["guarantees"][6]
    assert len(stp["source"]) <= 200 and "EXPIRE_TAKER" in stp["source"]
    # spot10k's entries that still hold, and the rule's
    assert len(stp["assumed"]) == len(one["assumed"]) + 4
    # what no source states is said to be so: the count of users and how
    # the rule meets the kinds
    assert "no public source" in " ".join(stp["assumed"])
    assert "no cited source" in stp["assumed"][-1]
    assert [a for a in one["assumed"] if "256 users" not in a] == [
        a for a in stp["assumed"][:len(one["assumed"])] if "16 users" not in a]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, wherever later PRs put theirs
    entry = by_name(bench["configs"], "spot10k_stp")
    assert entry["source"] == stp["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/spot10k_stp.json"
    cell = by_name(bench["workloads"], CELL)
    assert cell == dict(name=CELL, config="spot10k_stp", traffic="sat",
                        chips=1, why=cell["why"])
    assert len(cell["why"]) <= 200
    # the rule's name is the program's own
    from gome_tpu.types import SELF_TRADE_RULES

    assert "expire_taker" in SELF_TRADE_RULES


def test_the_cell_is_spot10k_sats_traffic_with_the_twelve_named_metrics():
    cell = spec.load_cell(CELL)
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) == (
        1, "spot10k_stp", "sat")
    assert cell["traffic"] == spec.load_cell("spot10k.sat")["traffic"]
    assert [m["name"] for m in cell["per_layer"]] == PER_LAYER
    assert [m["name"] for m in cell["end_to_end"]] == ["orders_per_s",
                                                       "setup_s"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == ["orders_per_s"] + PER_LAYER


def test_the_durable_cell_keeps_what_its_own_test_held_of_the_benchmark():
    """tests/benchmark/test_bench_durable_cell.py holds `configs[-1]` and
    `workloads[-1]` of BENCHMARK.json to the durable venue (lines 125-130),
    new entries go last, and a file the benchmark has is not this PR's to
    edit: that test stops there since this venue was added. What it held
    from there on is held here, the entries found by name."""
    durable = config_of("spot10k_durable")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = by_name(bench["configs"], "spot10k_durable")
    assert entry["reduced"] == [] and entry["source"] == durable["source"]
    name = "spot10k_durable.sat"
    listed = by_name(bench["workloads"], name)
    assert listed == dict(listed, config="spot10k_durable", traffic="sat",
                          chips=1)
    cell = spec.load_cell(name)
    assert cell["traffic"] == spec.load_cell("spot10k.sat")["traffic"]
    assert [m["name"] for m in cell["end_to_end"]] == ["orders_per_s",
                                                       "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 15 and names[-4:] == [
        "order_log_append_ms_per_request.sat",
        "match_log_append_ms_per_frame.sat", "snapshot_cut_ms.sat",
        "snapshot_write_share.sat"]
    for metric in names[-4:]:
        meta, _read = spec.load_reader(cell["base"], metric)
        assert meta["span"] == meta["spans"][0] and len(meta["spans"]) == 11
    with open(os.path.join(ROOT, durable["reference"])) as f:
        assert "gome_tpu" not in f.read()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, config_of("spot10k_stp")["reference"])) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["import bisect", "from collections import deque",
                       "from benchmark import reference"]
    assert "gome_tpu" not in source.replace("lxalano/gome", "")
    with open(os.path.join(spec.HERE, "reference.py")) as f:
        assert "gome_tpu" not in f.read().replace(
            "nothing from gome_tpu", "")


# -- the reference and the program's own oracle, under the rule ---------------


def small_flow(seed, n=700, kinds=(0, 0, 0, 1, 3, 4, 6), users=3):
    """Stream columns of a seeded flow over three symbols and a few levels:
    most adds meet resting orders, a third of those the taker's own."""
    rng = random.Random(seed)
    cols = {k: [] for k in stream.COLUMNS}
    targets = []
    for i in range(n):
        if targets and rng.random() < 0.15:
            sym, oid, side, price, uid = rng.choice(targets)
            row = (sym, uid, oid, side, 0, True,
                   price + (rng.random() < 0.2), 1)
        else:
            kind = rng.choice(kinds)
            row = (rng.randrange(3), rng.randrange(users), i,
                   rng.randrange(2), kind, False,
                   1_000 + rng.randint(-4, 4), rng.randint(1, 30))
            if kind != 1:
                targets.append((row[0], i, row[3], row[6], row[1]))
        for k, v in zip(stream.COLUMNS, row):
            cols[k].append(v)
    return cols


def oracle_events(cols, rule):
    from gome_tpu.oracle import OracleEngine
    from gome_tpu.types import Action, Order, OrderType, Side

    oracle = OracleEngine(self_trade=rule)
    got = []
    for i, (sym, uid, oid, side, kind, is_cancel, price, volume) in enumerate(
            zip(*(cols[k] for k in stream.COLUMNS))):
        order = Order(
            uuid=f"u{uid}", oid=str(oid), symbol=f"s{sym}", side=Side(side),
            price=price, volume=0 if is_cancel else volume,
            action=Action.DEL if is_cancel else Action.ADD,
            order_type=OrderType(0 if is_cancel else kind))
        for e in oracle.process(order):
            t, m = e.node, e.match_node
            got.append((
                i, int(t.symbol[1:]), int(t.uuid[1:]), int(t.oid),
                int(t.side), t.price, t.volume, int(m.uuid[1:]), int(m.oid),
                int(m.side), m.price, m.volume, e.match_volume))
    return got, oracle


@pytest.fixture(scope="module")
def venue_reference():
    return spec.load_reference(ROOT, config_of("spot10k_stp"))


@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 2147483659])
def test_the_reference_and_the_oracle_under_the_rule_agree_event_for_event(
        seed, venue_reference):
    cols = small_flow(seed)
    want = venue_reference.run(cols)
    got, oracle = oracle_events(cols, "expire_taker")
    assert got == want
    assert len(want) > 150 and oracle.stats.stp_expired > 40
    assert min(oracle.stats.expired_ioc, oracle.stats.fok_killed,
               oracle.stats.post_only_blocked) > 5
    assert not any(e[2] == e[7] and e[12] for e in want)  # no self-trade
    # without the rule both are another venue, and the plain Book is not
    # this one's
    assert oracle_events(cols, "none")[0] != want
    assert reference.run(cols) != want


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_on_the_venues_two_kinds_the_rule_alone_parts_it_from_the_plain_book(
        seed, venue_reference):
    """Limit and market orders, which the plain Book knows too: with the
    flow's three users the two Books differ, and only the plain one fills an
    account against itself; with an owner per order they agree; with one
    owner for all nothing trades under the rule."""
    cols = small_flow(seed, kinds=(0, 0, 0, 1))
    want = venue_reference.run(cols)
    plain = reference.run(cols)
    assert want != plain and not any(e[2] == e[7] and e[12] for e in want)
    assert any(e[2] == e[7] and e[12] for e in plain)
    assert oracle_events(cols, "expire_taker")[0] == want
    apart = dict(cols, uid=list(range(len(cols["uid"]))))
    # a cancel's uid is its request's: the plain Book takes no notice of it
    assert venue_reference.run(apart) == reference.run(apart)
    alone = dict(cols, uid=[0] * len(cols["uid"]))
    assert not any(e[12] for e in venue_reference.run(alone))


# -- the venue's stream -----------------------------------------------------------


@pytest.fixture(scope="module")
def streams():
    flow, path = rehearsal_flow()
    return flow, {seed: stream.generate(flow, seed, 40, R, reference_path=path)
                  for seed in (1, 2, 2147483659)}


def test_no_event_of_the_stream_has_one_uid_on_both_sides_and_adds_do_stop(
        streams, venue_reference):
    flow, made = streams
    assert flow["users"] == 16
    for m in made.values():
        events = np.asarray(m["events"], np.int64).reshape(-1, 13)
        fills = events[events[:, 12] > 0]
        assert len(fills) > 500 and (fills[:, 2] != fills[:, 7]).all()
        cols = {k: v.tolist() for k, v in m["cols"].items()}
        assert (np.array(venue_reference.run(cols), np.int64).reshape(-1, 13)
                == events).all()
        # by the venue's own Book: adds that stopped at their owner's order
        # (volume left, nothing rested, though a limit add's would have)
        books, out, stopped, adds = {}, [], set(), 0
        for i, row in enumerate(zip(*(cols[k] for k in stream.COLUMNS))):
            sym, uid, oid, side, kind, is_cancel, price, volume = row
            book = books.setdefault(sym, venue_reference.Book())
            if is_cancel:
                book.cancel(i, sym, uid, oid, side, price, out.append)
                continue
            del out[:]
            adds += 1
            rested = book.add(i, sym, uid, oid, side, kind, price, volume,
                              out.append)
            filled = sum(e[12] for e in out)
            if kind == 0 and not rested and filled < volume:
                stopped.add(oid)
        assert 0.005 < len(stopped) / adds < 0.2, (len(stopped), adds)
        # no cancel aims at an add that expired: it never rested
        assert not stopped & set(
            m["cols"]["oid"][m["cols"]["cancel"]].tolist())
    assert set(stream.facts(made[1], R, flow)) >= {"events_per_order"}


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_the_plain_book_and_the_control_mismatch_on_the_venues_stream(
        seed, streams, venue_reference):
    """The comparison sees the rule, not only the order of a level: the
    plain Book, which lets an account fill its own order, is far from the
    venue's events; so is the venue's own Book with time priority
    reversed."""
    _flow, made = streams
    m = made[seed]
    n = 40 * R
    sound = compare.expected_rows(m["events"], n)
    assert compare.compare_events(sound, sound)["events.mismatched"] == 0
    cols = {k: v.tolist() for k, v in m["cols"].items()}
    plain = np.array(reference.run(cols), np.int64).reshape(-1, 13)
    numbers = compare.compare_events(compare.expected_rows(plain, n), sound)
    assert numbers["events.mismatched"] + numbers["events.extra"] > 50
    broken = compare.control(m["cols"], n, sound,
                             venue_reference.CONTROL_PRIORITY,
                             venue_reference.run)
    assert broken["events.mismatched"] > 100


# -- the cell's rehearsal, its control, and the rule switched off -----------------


@pytest.fixture(scope="module")
def rule_off_root(tmp_path_factory):
    """A copy of the benchmark whose spot10k_stp.json lacks
    engine.self_trade: the program matches as spot10k does, on the cell's
    stream, against the venue's reference."""
    root = str(tmp_path_factory.mktemp("stp_rule_off"))
    base = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = config_of("spot10k_stp")
    del config["service"]["engine"]["self_trade"]
    with open(os.path.join(base, "configs", "spot10k_stp.json"), "w") as f:
        json.dump(config, f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture(scope="module")
def rehearsals(linked_root, rule_off_root, finish):
    runs = {
        "cell": (linked_root("stp_cell"), "--trace", "1", "--control"),
        "rule_off": (rule_off_root, "--trace", "0"),
    }
    procs = {
        key: subprocess.Popen(
            [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
             "--seconds", "2", "--rehearsal", "--root", root, *more],
            cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for key, (root, *more) in runs.items()
    }
    return {key: finish(key, p) for key, p in procs.items()}


def test_the_cells_rehearsal_is_correct_and_its_control_is_not(rehearsals):
    out, lines, stderr = rehearsals["cell"]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    assert any("control_correct False (has to be False)" in ln for ln in lines)
    report = json.loads(next(ln for ln in lines if "] report {" in ln)
                        .split("] report ", 1)[1])
    grids = report["grids_by_kernel"]
    assert grids and all(k.startswith("interpret") for k in grids), grids
    assert report["scan_giveways"] == {}
    assert report["rewinds"]["fallbacks"] == 0
    assert 0.2 < report["events_per_order"] < 0.6
    # the program's own counter, logged at stop: adds stopped at their
    # owner's order, under the venue's rule
    line = next(ln for ln in stderr.splitlines() if "adds by kind" in ln)
    assert "(self_trade expire_taker)" in line, line
    stopped = int(line.split(" stopped at their owner's")[0].split()[-1])
    assert stopped > 20, line
    would = set(out["metrics_that_a_chip_run_would_report"])
    device_only = {"kernel_us_per_op.sat", "match_kernel_roofline.sat",
                   "device_idle_share.sat"}
    assert set(PER_LAYER) - device_only <= would


def test_the_same_stream_against_a_service_without_the_rule_is_not_correct(
        rehearsals):
    out, lines, stderr = rehearsals["rule_off"]
    assert out["correct"] is False
    compared = out["compared"]
    wrong = (compared["events.mismatched"]["value"]
             + compared["events.extra"]["value"]
             + compared["events.missing"]["value"])
    assert wrong > 50, compared
    line = next(ln for ln in stderr.splitlines() if "adds by kind" in ln)
    assert "0 stopped at their owner's order (self_trade none)" in line, line

"""`spot10k_tif` and its cell `spot10k_tif.sat` (PR 34): spot10k's venue with
adds that carry a time in force. The configuration is spot10k's but for the
kinds, its stream has the same count per kind in every seed and is another
venue's to the plain Book, the cell's rehearsal is `correct` and its control is
not, and `tif_toy`, whose kind the program does not know, comes out `correct`
the moment its kind byte is one the program does know."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import compare, reference, spec, stream

from test_bench_stream import PARENT_DIGESTS

ROOT = spec.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
CELL = "spot10k_tif.sat"
KINDS = dict(limit=0, post_only=6, ioc=3, fok=4)
R = 128


def config_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def rehearsal_flow():
    config = config_of("spot10k_tif")
    spec._merge(config, config["rehearsal"])
    return config["flow"], os.path.join(ROOT, config["reference"])


def test_the_configuration_is_spot10ks_but_for_the_kinds():
    tif, one = config_of("spot10k_tif"), config_of("spot10k")
    flow = dict(tif["flow"])
    kinds = flow.pop("add_kinds")
    assert flow == one["flow"]
    assert {k["name"]: k["kind"] for k in kinds} == KINDS
    assert [k["share_of_adds"] for k in kinds] == [0.35, 0.25, 0.25, 0.15]
    for key in ("service", "rehearsal", "deployment", "log_level",
                "scan_giveways_allowed"):
        assert tif[key] == one[key], key
    assert tif["reduced"] == [] and tif["guarantees"][:5] == one["guarantees"]
    assert len(tif["guarantees"]) == 8 and len(tif["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "spot10k_tif")
    assert entry["source"] == tif["source"] and entry["reduced"] == []
    cell = spec.load_cell(CELL)
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) == (
        1, "spot10k_tif", "sat")
    assert cell["traffic"] == spec.load_cell("spot10k.sat")["traffic"]
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 12 and names[-1] == "events_per_order.sat"
    assert [m["name"] for m in cell["end_to_end"]] == ["orders_per_s",
                                                       "setup_s"]
    # the kinds' numbers are the program's own
    from gome_tpu.types import OrderType

    assert {k.name.lower(): int(k) for k in OrderType
            if k.name.lower() in KINDS} == KINDS


@pytest.mark.parametrize(
    "case", sorted(c for c in PARENT_DIGESTS if c[0] == "spot10k" and c[1]),
    ids=str)
def test_without_its_kinds_the_flow_makes_spot10ks_stream(case):
    """The venue differs from spot10k by its add kinds alone: take them out
    and the generator writes the accepted venue's bytes."""
    _venue, _rehearsal, request_orders, n_requests, seed = case
    flow, path = rehearsal_flow()
    del flow["add_kinds"]
    made = stream.generate(flow, seed, n_requests, request_orders,
                           reference_path=path)
    h = hashlib.sha256()
    for col in stream.COLUMNS:
        h.update(np.ascontiguousarray(made["cols"][col]).tobytes())
    h.update(np.ascontiguousarray(made["events"], dtype=np.int64).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[case]


@pytest.fixture(scope="module")
def streams():
    flow, path = rehearsal_flow()
    return flow, {seed: stream.generate(flow, seed, 40, R, reference_path=path)
                  for seed in (1, 2, 2147483659)}


def test_every_request_has_the_same_count_per_kind_in_three_seeds(streams):
    flow, made = streams
    counts = []
    for m in made.values():
        cols = m["cols"]
        code = np.where(cols["cancel"], 7, cols["kind"])  # 7: a cancel
        counts.append(np.stack([
            np.bincount(code[k * R:(k + 1) * R], minlength=8)
            for k in range(40)]))
    assert (counts[0] == counts[1]).all() and (counts[0] == counts[2]).all()
    shares = [stream.facts(m, R, flow)["add_kind_shares"]
              for m in made.values()]
    assert shares[0] == shares[1] == shares[2]
    assert set(shares[0]) == set(KINDS)
    assert all(share > 0.04 for share in shares[0].values())
    total = counts[0].sum(axis=0)
    assert total[2] == total[5] == 0 and min(total[[0, 1, 3, 4, 6, 7]]) > 150


def test_all_five_outcomes_occur_and_none_leaves_an_event_or_a_target(
        streams):
    """By the venue's own Book: an IOC add is dropped with lots left, a FOK
    add fills whole or not at all, a post-only add rests or takes nothing;
    and no cancel aims at an IOC or FOK add (they never rest)."""
    _flow, made = streams
    module = spec.load_reference(ROOT, config_of("spot10k_tif"))
    seen = dict(ioc_dropped=0, ioc_whole=0, fok_filled=0, fok_killed=0,
                post_rested=0, post_blocked=0)
    for m in made.values():
        cols = {k: v.tolist() for k, v in m["cols"].items()}
        assert (np.array(module.run(cols), np.int64).reshape(-1, 13)
                == m["events"]).all()
        books, events = {}, []
        for i, row in enumerate(zip(*(cols[k] for k in stream.COLUMNS))):
            sym, uid, oid, side, kind, is_cancel, price, volume = row
            book = books.setdefault(sym, module.Book())
            if is_cancel:
                book.cancel(i, sym, uid, oid, side, price, events.append)
                continue
            del events[:]
            rested = book.add(i, sym, uid, oid, side, kind, price, volume,
                              events.append)
            filled = sum(e[12] for e in events)
            if kind == KINDS["ioc"]:
                assert not rested
                seen["ioc_dropped" if filled < volume else "ioc_whole"] += 1
            elif kind == KINDS["fok"]:
                assert not rested and filled in (0, volume)
                seen["fok_filled" if filled else "fok_killed"] += 1
            elif kind == KINDS["post_only"]:
                assert filled == 0
                seen["post_rested" if rested else "post_blocked"] += 1
        never_rest = np.isin(m["cols"]["kind"], [3, 4]) & ~m["cols"]["cancel"]
        assert not set(m["cols"]["oid"][never_rest].tolist()) & set(
            m["cols"]["oid"][m["cols"]["cancel"]].tolist())
    assert min(seen.values()) > 10, seen


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_the_plain_book_and_the_control_mismatch_on_the_venues_stream(
        seed, streams):
    """The comparison sees the mechanism, not only the order of a level: the
    plain Book, which rests an IOC remainder, half-fills a FOK add and lets a
    post-only add take, is far from the venue's events; so is the venue's own
    Book with time priority reversed."""
    _flow, made = streams
    m = made[seed]
    n = 40 * R
    sound = compare.expected_rows(m["events"], n)
    assert compare.compare_events(sound, sound)["events.mismatched"] == 0
    cols = {k: v.tolist() for k, v in m["cols"].items()}
    plain = np.array(reference.run(cols), np.int64).reshape(-1, 13)
    numbers = compare.compare_events(compare.expected_rows(plain, n), sound)
    assert numbers["events.mismatched"] > 100
    module = spec.load_reference(ROOT, config_of("spot10k_tif"))
    broken = compare.control(m["cols"], n, sound, module.CONTROL_PRIORITY,
                             module.run)
    assert broken["events.mismatched"] > 100


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with `tif_toy` in it, its kind byte set to 3:
    the number the program knows immediate-or-cancel by."""
    root = str(tmp_path_factory.mktemp("tif_known"))
    base = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(DATA, "tif_toy.json")) as f:
        config = json.load(f)
    ioc = next(k for k in config["flow"]["add_kinds"] if k["name"] == "ioc")
    assert ioc["kind"] == 2
    ioc["kind"] = 3
    with open(os.path.join(base, "configs", "tif_toy.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(DATA, "tif_toy_reference.py")) as f:
        source = f.read()
    assert "IOC = 2\n" in source
    with open(os.path.join(base, "configs", "tif_toy_reference.py"), "w") as f:
        f.write(source.replace("IOC = 2\n", "IOC = 3\n"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(
        name="tif_toy", source="test", reduced=[], why="test",
        file="benchmark/configs/tif_toy.json"))
    bench["workloads"].append(dict(
        name="tif_toy.sat", config="tif_toy", traffic="sat", chips=1,
        why="test"))
    bench["end_to_end"][0]["workloads"].append("tif_toy.sat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def rehearsals(linked_root, toy_root, finish):
    """The cell's traced rehearsal with its control, and the toy venue on a
    known kind, side by side, each in a root of its own."""
    runs = {
        "cell": (linked_root("tif_cell"), CELL, "--trace", "1", "--control"),
        "toy": (toy_root, "tif_toy.sat", "--trace", "0"),
    }
    procs = {
        key: subprocess.Popen(
            [sys.executable, RUN, "--workload", workload, "--seed",
             "2147483659", "--seconds", "2", "--rehearsal", "--root", root,
             *more],
            cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for key, (root, workload, *more) in runs.items()
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
    facts = json.loads(next(ln for ln in lines if "] stream " in ln)
                       .split("] stream ")[1])
    assert set(facts["add_kind_shares"]) == set(KINDS)
    # the program's own counters, logged at stop: every kind was applied and
    # each of the three ways to expire happened
    line = next(ln for ln in stderr.splitlines() if "adds by kind" in ln)
    for kind in ("'LIMIT'", "'MARKET'", "'IOC'", "'FOK'", "'POST_ONLY'"):
        assert kind in line, line
    counts = [int(part.split()[-1]) for part in
              line.split("expired: ")[1].replace(" IOC remainders dropped", "")
              .replace(" FOK killed", "").replace(" POST_ONLY blocked", "")
              .split(", ")]
    assert len(counts) == 3 and min(counts) > 0, line


def test_the_traced_rehearsal_names_the_cells_twelve_per_layer_metrics(
        rehearsals):
    out, _lines, _stderr = rehearsals["cell"]
    would = set(out["metrics_that_a_chip_run_would_report"])
    cell = spec.load_cell(CELL)
    # the kernel's two come from a device trace, which a CPU run has not
    device_only = {"kernel_us_per_op.sat", "match_kernel_roofline.sat",
                   "device_idle_share.sat"}
    assert {m["name"] for m in cell["per_layer"]} - device_only <= would
    assert "events_per_order.sat" in would
    # its idle gaps are attributed to the program's leaf spans
    names = spec.span_names(cell["base"], [m["name"]
                                           for m in cell["per_layer"]])
    assert {"grid_dispatch", "frame_pack", "frame_fetch", "frame_decode",
            "frame_admit", "gateway_admit", "feed_fanout", "consumer_poll",
            "feed_poll", "stream_wait"} <= set(names)


def test_events_per_order_reads_two_counters_and_nothing_where_one_lacks():
    meta, read = spec.load_reader(os.path.join(ROOT, "benchmark"),
                                  "events_per_order.sat")
    win = dict(c0=dict(feed_events=100, orders=1_000),
               c1=dict(feed_events=500, orders=2_000))
    assert read(dict(win=win), meta) == pytest.approx(0.4)
    del win["c1"]["feed_events"]
    assert read(dict(win=win), meta) is None


def test_tif_toy_on_a_kind_the_program_knows_comes_out_correct(rehearsals):
    """The sentence in test_bench_venues.py's docstring, made true: the same
    venue, rules and reference, and the kind byte the program knows
    immediate-or-cancel by."""
    out, lines, _stderr = rehearsals["toy"]
    assert out["correct"] is True, [ln for ln in lines if "FAIL" in ln]
    assert out["failed"] == 0 < out["attempted"]
    facts = json.loads(next(ln for ln in lines if "] stream " in ln)
                       .split("] stream ")[1])
    assert facts["add_kind_shares"]["ioc"] > 0.15

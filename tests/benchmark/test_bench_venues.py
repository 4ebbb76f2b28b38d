"""A venue with other semantics, and one with durable state, taken from new
files alone: tests/benchmark/data/ holds two toy venues, added to a copy of the
benchmark in a temporary root with no file of it edited, and run on the CPU
rehearsal. `tif_toy` has an order kind the program does not know, so the run
has to come out not correct by the venue's own Book; `durable_toy` is killed
and booted again on its directory and has to read every acknowledged order
back, twice in a row in one root, and not when a match frame is dropped or its
snapshots lie outside the run's directory and are stale."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec, stream

ROOT = spec.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
RESTART = ["restart.events_mismatched", "restart.events_missing",
           "restart.events_extra", "restart.books_mismatched",
           "restart.not_recovered"]


def digest_of_tree(folder):
    out = {}
    for base, _dirs, files in os.walk(folder):
        for fn in files:
            if "__pycache__" not in base:
                path = os.path.join(base, fn)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, folder)] = hash(f.read())
    return out


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark plus the toy venues' files and entries."""
    root = str(tmp_path_factory.mktemp("venues"))
    base = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest_of_tree(base)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    outside = os.path.join(root, "snapshots_outside_the_run_directory")
    for name in ("tif_toy", "durable_toy"):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(base, "configs"))
        shutil.copy(os.path.join(DATA, name + "_reference.py"),
                    os.path.join(base, "configs"))
    # the same venue with its snapshots left outside the run's directory
    with open(os.path.join(DATA, "durable_toy.json")) as f:
        stale = json.load(f)
    stale["name"] = "durable_stale"
    stale["service"]["persist"]["dir"] = outside
    with open(os.path.join(base, "configs", "durable_stale.json"), "w") as f:
        json.dump(stale, f)
    shutil.copy(os.path.join(DATA, "toy_sat.json"),
                os.path.join(base, "traffic"))
    for name in ("tif_toy", "durable_toy", "durable_stale"):
        bench["configs"].append(dict(
            name=name, source="test", reduced=[], why="test",
            file=f"benchmark/configs/{name}.json"))
        # a run that has to stall ends sooner under sat's own timeout
        for mix in ("sat", "toy_sat"):
            bench["workloads"].append(dict(
                name=f"{name}.{mix}", config=name, traffic=mix, chips=1,
                why="test"))
            bench["end_to_end"][0]["workloads"].append(f"{name}.{mix}")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = digest_of_tree(base)
    assert {k: after[k] for k in before} == before  # files added, none edited
    return dict(root=root, outside=outside)


def start(root, workload, seed, *more):
    return subprocess.Popen(
        [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", "2", "--trace", "0", "--rehearsal", "--root", root,
         *more],
        cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


@pytest.fixture(scope="module")
def runs(toy_root, finish):
    """The toy venues' rehearsals: the IOC venue and the run with a dropped
    match frame beside the three durable runs, which share a directory or a
    snapshot and so follow one another."""
    root, outside = toy_root["root"], toy_root["outside"]
    side = dict(tif=start(root, "tif_toy.sat", 2147483659),
                dropped=start(root, "durable_stale.sat", 7,
                              "--sabotage", "seq"))
    out = {}
    run_dir = os.path.join(root, ".bench_run", "durable_toy.toy_sat")
    for key, seed in (("first", 2147483659), ("second", 5)):
        out[key] = finish(key, start(root, "durable_toy.toy_sat", seed))[:2]
        out[key + ".kept"] = sorted(
            os.path.relpath(os.path.join(base, d), run_dir)
            for base, dirs, _files in os.walk(run_dir) for d in dirs)
    for key, p in side.items():
        out[key] = finish(key, p)[:2]
    # snapshots of another run, outside the run's directory: stale
    shutil.rmtree(outside, ignore_errors=True)
    shutil.copytree(os.path.join(run_dir, "snapshots"), outside)
    result, lines, stderr = finish(
        "stale", start(root, "durable_stale.toy_sat", 11), may_break=True)
    out["stale"] = (result, lines + stderr.splitlines())
    return out


def compared(lines, config):
    return {ln.split(f"compare {config} ")[1].split(" = ")[0]: ln
            for ln in lines if f"] compare {config} " in ln}


def test_the_generators_book_and_kinds_come_from_the_configurations_files(
        toy_root):
    """Expected events are that Book's replay, the kinds' shares the same in
    three seeds, and no cancel aims at an order that never rests."""
    cell = spec.load_cell("tif_toy.sat", toy_root["root"], rehearsal=True)
    flow = cell["config"]["flow"]
    path = os.path.join(toy_root["root"], cell["config"]["reference"])
    module = spec.load_reference(toy_root["root"], cell["config"])
    assert stream.book_class(path).__name__ == "Book"
    assert stream.book_class(path) is not stream.book_class(None)
    shares = []
    for seed in (1, 2, 2147483659):
        made = stream.generate(flow, seed, 40, 128, workers=2,
                               reference_path=path)
        cols = made["cols"]
        replayed = np.array(
            module.run({k: v.tolist() for k, v in cols.items()}),
            np.int64).reshape(-1, 13)
        assert (replayed == made["events"]).all()
        plain = stream.generate(flow, seed, 40, 128)
        assert len(plain["events"]) != len(made["events"])
        ioc = set(cols["oid"][(cols["kind"] == 2) & ~cols["cancel"]].tolist())
        assert len(ioc) > 500
        assert not ioc & set(cols["oid"][cols["cancel"]].tolist())
        shares.append(stream.facts(made, 128, flow)["add_kind_shares"])
    assert shares[0] == shares[1] == shares[2]
    assert set(shares[0]) == {"limit", "ioc"} and shares[0]["ioc"] > 0.15
    with pytest.raises(ValueError, match="sum to"):
        stream.add_kinds(dict(add_kinds=[dict(
            name="ioc", kind=2, share_of_adds=0.5, pricing="marketable")]))
    with pytest.raises(ValueError, match="pricing"):
        stream.add_kinds(dict(add_kinds=[dict(
            name="ioc", kind=2, share_of_adds=1.0, pricing="aggressive")]))


def test_a_kind_the_program_does_not_know_comes_out_not_correct(runs):
    """The comparison follows the venue's rules: the day the program learns
    immediate-or-cancel, the same files come out correct."""
    out, lines = runs["tif"]
    assert out["correct"] is False
    facts = json.loads(next(ln for ln in lines if "] stream " in ln)
                       .split("] stream ")[1])
    assert facts["add_kind_shares"]["ioc"] > 0.15
    numbers = compared(lines, "tif_toy")
    assert "(limit 0) FAIL" in numbers["events.mismatched"]
    assert out["compared"]["events.mismatched"]["value"] > 0
    assert not any(name.startswith("restart.") for name in numbers)


@pytest.mark.parametrize("key", ["first", "second"])
def test_a_durable_venue_reads_every_acknowledged_order_back(key, runs,
                                                             toy_root):
    """Twice in a row in one root: the second run must not see the first's
    log, and each is killed, booted again and held to the reference."""
    out, lines = runs[key]
    numbers = compared(lines, "durable_toy")
    assert [n for n in numbers if n.startswith("restart.")] == RESTART
    assert all("= 0 (limit 0) ok" in numbers[n] for n in RESTART)
    assert out["correct"] is True, [ln for ln in lines if "FAIL" in ln]
    kept = runs[key + ".kept"]  # its log and snapshots: under the directory
    assert kept[:2] == ["bus_data", "snapshots"] and len(kept) > 2
    assert all(d.startswith("snapshots/snap-") for d in kept[2:])
    assert not os.path.exists(os.path.join(ROOT, "bus_data"))
    ready = json.loads(next(ln for ln in lines if "] serving ready: " in ln)
                       .split("ready: ")[1])
    assert ready["run_dir_disk"]["filesystem"] != ""
    assert ready["run_dir_disk"]["fsync_64k_median_ms"] > 0
    again = json.loads(next(ln for ln in lines if "] restart {" in ln)
                       .split("] restart ")[1])
    assert again["restart_s"] > 0 and again["events_from_second"] > 0
    # each seq once: the second process starts at or before what was held
    assert again["second_from_seq"] <= again["events_from_first_process"]


@pytest.mark.parametrize("key, numbers_failed", [
    ("dropped", ["events.missing", "matchfeed.gaps"]),
    ("stale", []),
])
def test_a_durable_venue_broken_underneath_comes_out_not_correct(
        key, numbers_failed, runs):
    """A dropped match frame; snapshots of another run where the run's
    emptied directory cannot reach them. The program may refuse to boot on
    the stale state (exit 1, no result) or serve wrongly: never `correct`."""
    out, lines = runs[key]
    if out is None:
        assert key == "stale" and any("serving process exited" in ln
                                      for ln in lines)
        return
    assert out["correct"] is False
    failed = [ln for ln in lines if ln.endswith("FAIL")]
    assert failed
    for number in numbers_failed:
        assert any(f" {number} = " in ln for ln in failed), failed

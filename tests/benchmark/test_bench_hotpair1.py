"""`hotpair1` and its cell `hotpair1.sat` (PR 43): the reference's own venue,
one pair on one lane, 1,280-1,792 resting a side in the 4096-slot class. The
configuration is hotpair8's but for the keys that make it one pair; its
stream holds the band in every seed and is the same bytes from any number of
workers; its plain reference imports nothing of the program; the cell's
rehearsal is `correct` at `n_slots 1` with every grid on the kernel, and its
control is not. Every entry is found by name: nothing here pins a position
in `BENCHMARK.json`."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import compare, reference, spec, stream

ROOT = spec.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
CELL = "hotpair1.sat"
R = 128
PER_LAYER = [
    "outstanding_mean.sat", "admit_us_per_order.sat",
    "order_backlog_frames.sat", "feed_ms_per_frame.sat",
    "device_calls_per_frame.sat", "rewinds_in_window.sat",
    "kernel_us_per_op.sat", "match_kernel_roofline.sat",
    "device_idle_share.sat", "publish_ms_per_frame.sat",
    "fanout_us_per_event.sat", "grid_rows_per_frame.sat",
    "events_per_order.sat", "stream_send_ms_per_frame.sat",
]


def config_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_flow():
    config = config_of("hotpair1")
    spec._merge(config, config["rehearsal"])
    return config["flow"], os.path.join(ROOT, config["reference"])


# -- the files and the entries -------------------------------------------------


def test_the_configuration_is_hotpair8s_but_for_the_keys_that_make_it_one_pair():
    one, eight = config_of("hotpair1"), config_of("hotpair8")
    assert list(one) == list(eight)  # key for key, in hotpair8's order
    assert (one["service"]["engine"].pop("n_slots"),
            eight["service"]["engine"].pop("n_slots")) == (1, 8)
    assert (one["service"]["engine"].pop("cap"),
            eight["service"]["engine"].pop("cap")) == (4096, 1024)
    assert (one["flow"].pop("symbols"), eight["flow"].pop("symbols")) == (1, 8)
    assert one["flow"].pop("bands") == [
        {"ranks": [1, 1], "band": [1280, 1792]}]
    assert eight["flow"].pop("bands") == [
        {"ranks": [1, 8], "band": [320, 448]}]
    assert one.pop("rehearsal") == {
        "service": {"engine": {"n_slots": 1, "cap": 64, "max_t": 8}},
        "flow": {"bands": [{"ranks": [1, 1], "band": [24, 56]}],
                 "opening": {"orders": 512}}}
    eight.pop("rehearsal")
    differ = sorted(k for k in one if one[k] != eight[k])
    assert differ == ["assumed", "deployment", "name", "reference", "source"]
    assert one["service"] == eight["service"] and one["flow"] == eight["flow"]
    assert one["guarantees"] == eight["guarantees"]  # word for word
    assert one["name"] == "hotpair1" and one["reduced"] == []
    assert one["scan_giveways_allowed"] == []
    assert one["reference"] == "benchmark/configs/hotpair1_reference.py"
    # the source's shape: one symbol, 100 levels a side, 1-100 lots
    flow = one["flow"]
    assert flow["band"] // flow["tick"] == 100 and flow["lots"] == [1, 100]
    assert len(one["source"]) <= 200
    for word in ("doorder.go:37-59", "eth2usdt", "configs[0]+configs[1]",
                 "delorder.go"):
        assert word in one["source"], word
    said = " ".join(one["assumed"])
    for word in ("no public source", "4096", "1,280-1,792", "256 users",
                 "n_slots 1", "seed 23"):
        assert word in said, word


def test_the_benchmarks_entries_are_found_by_name():
    b = bench()
    one = config_of("hotpair1")
    entry = by_name(b["configs"], "hotpair1")
    assert entry == dict(name="hotpair1", source=one["source"],
                         file="benchmark/configs/hotpair1.json", reduced=[],
                         why=entry["why"])
    assert len(entry["why"]) <= 200
    cell = by_name(b["workloads"], CELL)
    assert cell == dict(name=CELL, config="hotpair1", traffic="sat", chips=1,
                        why=cell["why"])
    assert len(cell["why"]) <= 200 and "one lane" in cell["why"]
    listed = [m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == ["orders_per_s"] + PER_LAYER
    # appended: the cell is the last of every list that names it
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]
    # no file of the venue's own beside the two: no reader, no metric file
    files = sorted(f for f in os.listdir(os.path.join(spec.HERE, "configs"))
                   if f.startswith("hotpair1"))
    assert files == ["hotpair1.json", "hotpair1_reference.py"]
    assert not os.path.exists(os.path.join(spec.HERE, "cells", CELL + ".json"))


def test_the_cell_is_sats_traffic_with_the_fourteen_named_metrics():
    cell = spec.load_cell(CELL)
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) == (
        1, "hotpair1", "sat")
    assert cell["traffic"] == spec.load_cell("hotpair8.sat")["traffic"]
    assert [m["name"] for m in cell["per_layer"]] == PER_LAYER
    assert [m["name"] for m in cell["end_to_end"]] == ["orders_per_s",
                                                       "setup_s"]
    # every metric's reader loads, and events_per_order's file names the
    # program's leaf spans, so the cell's idle gaps are attributed
    for metric in PER_LAYER:
        meta, read = spec.load_reader(cell["base"], metric)
        assert callable(read), metric
    meta, _read = spec.load_reader(cell["base"], "events_per_order.sat")
    assert {"grid_dispatch", "frame_pack", "frame_fetch", "consumer_poll",
            "feed_poll", "stream_wait"} <= set(meta["spans"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, config_of("hotpair1")["reference"])) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from benchmark import reference"]
    assert "gome_tpu" not in source
    module = spec.load_reference(ROOT, config_of("hotpair1"))
    assert (module.PRIORITY, module.CONTROL_PRIORITY) == ("fifo", "lifo")
    assert stream.book_class(
        os.path.join(ROOT, config_of("hotpair1")["reference"])
    ) is reference.Book


# -- the stream on one lane ---------------------------------------------------


@pytest.fixture(scope="module")
def streams():
    flow, path = rehearsal_flow()
    return flow, {seed: stream.generate(flow, seed, 40, R, reference_path=path)
                  for seed in (1, 2, 2147483659)}


@pytest.mark.parametrize("seed", [1, 2, 2147483659])
def test_the_one_lane_stays_inside_its_band_from_the_listing_on(seed, streams):
    flow, made = streams
    m = made[seed]
    assert set(m["cols"]["sym"].tolist()) == {0}
    assert stream.traced_ranks(flow) == [0] == sorted(m["traces"])
    lo, hi = stream.band_of(flow, 0)
    assert (lo, hi) == (24, 56)
    tr = m["traces"][0]
    inside = np.flatnonzero((tr[:, 0] >= lo) & (tr[:, 3] >= lo))
    assert len(inside) and inside[0] <= 4
    after = tr[inside[0]:]
    assert after[:, [0, 3]].min() >= lo and after[:, [1, 4]].max() <= hi
    # the mix the file states, on one lane: cancels 45 %, a quarter of the
    # adds market orders; the listing (passive quotes) comes first
    cols = m["cols"]
    n_listing = len(stream.listing_plan(flow))
    body = slice(n_listing, None)
    assert 0.40 < cols["cancel"][body].mean() < 0.50
    adds = ~cols["cancel"][body]
    assert 0.20 < (cols["kind"][body][adds] == 1).mean() < 0.30
    assert set(stream.facts(m, R, flow)) >= {"events_per_order"}


def test_at_the_cells_own_size_the_band_is_the_4096_slot_class():
    """The venue's own flow (not the rehearsal's): 1,280-1,792 a side from
    the listing on, so the resting count alone is over the 1024-slot class
    at every pack; 12 requests of 4,096 orders on one lane."""
    config = config_of("hotpair1")
    flow = config["flow"]
    m = stream.generate(flow, 1873402117, 12, 4096,
                        reference_path=os.path.join(ROOT, config["reference"]))
    lo, hi = stream.band_of(flow, 0)
    assert (lo, hi) == (1280, 1792)
    n_listing = len(stream.listing_plan(flow))
    assert 2 * lo < n_listing < 2 * hi  # the listing stands it in the band
    tr = m["traces"][0]
    after = tr[1:]  # request 0 holds the listing
    assert after[:, [0, 3]].min() >= lo and after[:, [1, 4]].max() <= hi
    assert after[:, [0, 3]].min() > 1024  # never the 1024-slot class
    events_per_order = len(m["events"]) / (12 * 4096)
    assert 0.45 < events_per_order < 0.62, events_per_order


@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("seed", [2, 2147483659])
def test_any_number_of_workers_gives_the_same_bytes(seed, workers, streams):
    """One lane is one worker's; the others get nothing and the merge is
    the single process's output."""
    flow, made = streams
    _flow, path = rehearsal_flow()
    again = stream.generate(flow, seed, 40, R, workers=workers,
                            reference_path=path)

    def digest(m):
        h = hashlib.sha256()
        for col in stream.COLUMNS:
            h.update(np.ascontiguousarray(m["cols"][col]).tobytes())
        h.update(np.ascontiguousarray(m["events"], dtype=np.int64).tobytes())
        return h.hexdigest()

    assert digest(again) == digest(made[seed])


def test_the_streams_events_are_the_references_own(streams):
    _flow, made = streams
    module = spec.load_reference(ROOT, config_of("hotpair1"))
    cols = {k: v.tolist() for k, v in made[2]["cols"].items()}
    replay = np.array(module.run(cols), np.int64)
    assert (replay == made[2]["events"]).all()


@pytest.mark.parametrize("seed", [1, 2, 2147483659])
def test_the_lifo_control_comes_out_not_correct_on_one_lane(seed, streams):
    _flow, made = streams
    m = made[seed]
    n = 40 * R
    sound = compare.expected_rows(m["events"], n)
    assert compare.compare_events(sound, sound)["events.mismatched"] == 0
    module = spec.load_reference(ROOT, config_of("hotpair1"))
    broken = compare.control(m["cols"], n, sound, module.CONTROL_PRIORITY,
                             module.run)
    assert broken["events.mismatched"] > 100


# -- the cell's rehearsal and its control ---------------------------------------


@pytest.fixture(scope="module")
def rehearsal(linked_root, finish):
    p = subprocess.Popen(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "2", "--rehearsal", "--root", linked_root("hotpair1"),
         "--trace", "1", "--control"],
        cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    return finish("hotpair1", p)


def test_the_cells_rehearsal_is_correct_and_its_control_is_not(rehearsal):
    out, lines, _stderr = rehearsal
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    assert any("control_correct False (has to be False)" in ln for ln in lines)
    would = set(out["metrics_that_a_chip_run_would_report"])
    # (a rehearsal's small frames run one program a grid, which the
    # benchmark's wrapper round BatchEngine._step does not see: no rows)
    device_only = {"kernel_us_per_op.sat", "match_kernel_roofline.sat",
                   "device_idle_share.sat", "grid_rows_per_frame.sat"}
    assert set(PER_LAYER) - device_only <= would


def test_a_venue_of_one_lane_runs_every_grid_on_the_kernel(rehearsal):
    """n_slots 1 as the file says it: every grid a full one on the kernel
    (interpreted here), none on the scan path, one lane in one class from
    the first pack to the last."""
    _out, lines, stderr = rehearsal
    report = json.loads(next(ln for ln in lines if "] report {" in ln)
                        .split("] report ", 1)[1])
    assert set(report["grids_by_kernel"]) == {"interpret_full"}
    assert report["scan_giveways"] == {}
    assert report["rewinds"]["fallbacks"] == 0
    assert report["rewinds"]["escalations"] == 0
    assert report["lanes_by_class_start"] == report["lanes_by_class_end"] == {
        "64": 1}
    assert 0.45 < report["events_per_order"] < 0.62
    # the program's stop line: 8 rows for the venue's one lane
    line = next(ln for ln in stderr.splitlines() if "fast-path frames" in ln)
    assert "the book stack holds 8 rows for the venue's 1 lanes" in line, line

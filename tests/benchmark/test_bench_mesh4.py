"""`spot10k_mesh4` and its cell `spot10k.sat4`: spot10k's stream byte for byte,
the cell's rehearsal on four CPU devices (and its control), and the readers the
cell brings, which on one device read what the accepted kernel readers read."""

import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec, stream, tracered

from test_bench_stream import PARENT_DIGESTS

ROOT = spec.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: run.py hands its environment to serve.py: the rehearsal's mesh needs four
#: CPU devices in that process (benchmark/configs/spot10k_mesh4.json says so).
ENV = dict({k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
           JAX_NUM_CPU_DEVICES="4")


def config_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_flow_is_spot10ks_key_for_key_and_only_the_mesh_differs():
    mesh4, one = config_of("spot10k_mesh4"), config_of("spot10k")
    assert mesh4["flow"] == one["flow"]
    assert mesh4["rehearsal"]["flow"] == one["rehearsal"]["flow"]
    engine = dict(mesh4["service"]["engine"])
    assert engine.pop("mesh_devices") == 4
    assert one["service"]["engine"]["mesh_devices"] == 0
    assert engine == {k: v for k, v in one["service"]["engine"].items()
                      if k != "mesh_devices"}
    assert {k: v for k, v in mesh4["service"].items() if k != "engine"} == \
        {k: v for k, v in one["service"].items() if k != "engine"}
    # (the rehearsal's mesh: one device as committed, four in this file's runs)
    assert mesh4["rehearsal"]["service"]["engine"]["mesh_devices"] == 1
    assert mesh4["reduced"] == [] and mesh4["guarantees"][:5] == one["guarantees"]
    cell = spec.load_cell("spot10k.sat4")
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) == (
        4, "spot10k_mesh4", "sat")
    assert cell["traffic"] == spec.load_cell("spot10k.sat")["traffic"]


@pytest.mark.parametrize(
    "case", sorted(c for c in PARENT_DIGESTS if c[0] == "spot10k"), ids=str)
def test_the_stream_has_spot10ks_digests(case):
    _venue, rehearsal, request_orders, n_requests, seed = case
    config = config_of("spot10k_mesh4")
    if rehearsal:
        spec._merge(config, config["rehearsal"])
    made = stream.generate(
        config["flow"], seed, n_requests, request_orders,
        reference_path=os.path.join(ROOT, config["reference"]))
    h = hashlib.sha256()
    for col in stream.COLUMNS:
        h.update(np.ascontiguousarray(made["cols"][col]).tobytes())
    h.update(np.ascontiguousarray(made["events"], dtype=np.int64).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[case]


def test_symbols_reach_the_engine_in_rank_order_in_every_seed():
    """What the placement stands on: the listing keeps its place at the head
    of the stream, rank by rank, so the k-th symbol to arrive is the symbol of
    rank k + 1 whatever the seed, and the deal puts ranks 1-24 six to a chip."""
    flow = config_of("spot10k_mesh4")["flow"]
    n = flow["symbols"]
    for seed in (2, 2147483659):
        ranks, _sym_of_rank = stream.layout(flow, seed, 4, 4096)
        _, first = np.unique(ranks, return_index=True)
        assert (np.argsort(first) == np.arange(n)).all()
    assert np.bincount(np.arange(24) % 4).tolist() == [6, 6, 6, 6]


REHEARSALS = {
    "sat4": ("--trace", "1"),
    "control": ("--trace", "0", "--control"),
}


@pytest.fixture(scope="module")
def four_device_root(tmp_path_factory):
    """make(tag) -> a root of its own whose copy of the configuration
    rehearses on a mesh of four: the committed file's rehearsal block keeps a
    mesh of one device, which every plain rehearsal can boot."""

    def make(tag: str) -> str:
        root = str(tmp_path_factory.mktemp(tag))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(root, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        path = os.path.join(root, "benchmark", "configs", "spot10k_mesh4.json")
        config = config_of("spot10k_mesh4")
        assert config["rehearsal"]["service"]["engine"]["mesh_devices"] == 1
        config["rehearsal"]["service"]["engine"]["mesh_devices"] = 4
        with open(path, "w") as f:
            json.dump(config, f)
        # side by side with other files' rehearsals a first interpreted
        # kernel under shard_map can take over the mix's 20 s to compile
        # (as tests/benchmark/data/toy_sat.json allows for)
        path = os.path.join(root, "benchmark", "traffic", "sat.json")
        with open(path) as f:
            mix = json.load(f)
        mix["rehearsal"]["stall_timeout_s"] = 60
        with open(path, "w") as f:
            json.dump(mix, f)
        return root

    return make


@pytest.fixture(scope="module")
def rehearsals(four_device_root, finish):
    """The cell's rehearsal on four CPU devices, traced, and the same with the
    control, side by side, each in a root of its own."""
    procs = {
        key: subprocess.Popen(
            [sys.executable, RUN, "--workload", "spot10k.sat4", "--seed",
             "2147483659", "--seconds", "2", "--rehearsal", "--root",
             four_device_root("mesh4_" + key), *more],
            cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for key, more in REHEARSALS.items()
    }
    return {key: finish(key, p) for key, p in procs.items()}


def report_of(lines):
    line = next(ln for ln in lines if "] report {" in ln)
    return json.loads(line.split("] report ", 1)[1])


def test_the_cell_rehearses_correct_on_four_cpu_devices(rehearsals):
    out, lines, _stderr = rehearsals["sat4"]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    report = report_of(lines)
    grids = report["grids_by_kernel"]
    assert grids and all(k.startswith("interpret") for k in grids), grids
    assert grids.get("interpret_dense", 0) > 0  # the per-shard dense path
    assert report["scan_giveways"] == {}
    # the per-layer metrics the traced rehearsal finds something to read for
    would = set(out["metrics_that_a_chip_run_would_report"])
    assert {"shard_put_ms_per_grid.sat", "grid_rows_per_frame.sat",
            "feed_ms_per_frame.sat", "device_calls_per_frame.sat"} <= would
    assert not {"kernel_us_per_op.sat", "match_kernel_roofline.sat"} & would
    # the idle gaps are attributed to the program's leaf spans here too
    names = spec.span_names(os.path.join(ROOT, "benchmark"), [
        m["name"] for m in spec.load_cell("spot10k.sat4")["per_layer"]])
    assert {"shard_put", "grid_dispatch", "frame_pack", "frame_fetch",
            "consumer_poll", "stream_wait", "pipeline_feed"} <= set(names)


def test_the_control_on_four_cpu_devices_comes_out_not_correct(rehearsals):
    out, lines, _stderr = rehearsals["control"]
    assert out["correct"] is True  # the program itself, beside its control
    assert any("control_correct False (has to be False)" in ln for ln in lines)
    mismatched = next(ln for ln in lines
                      if "] control spot10k_mesh4 (lifo) events.mismatched" in ln)
    assert int(mismatched.split(" = ")[1].split()[0]) > 0


# -- the readers the cell brings ---------------------------------------------

ENGINE = dict(max_fills=16)
GRID = (2048, 16, 64)  # rows, depth, cap class of the grids made up below


def run_of(trace, rows, ops_per_grid, n_grids=8, frames=4):
    """What run.py hands a reader, around a reduced trace: `n_grids` grids of
    `rows` x GRID[1:] noted in the window, carrying ops_per_grid ops each."""
    return dict(
        trace=tracered.reduce(trace), rehearsal=False,
        device_kind="TPU v5 lite",
        cell=dict(config=dict(service=dict(engine=ENGINE))),
        win=dict(t0_ns=0, t1_ns=1000,
                 c0=dict(kernel_grids=10, kernel_ops=1000, frames=3),
                 c1=dict(kernel_grids=10 + n_grids,
                         kernel_ops=1000 + n_grids * ops_per_grid,
                         frames=3 + frames)),
        grids=[(10 * i, rows, GRID[1], GRID[2], ops_per_grid)
               for i in range(n_grids)],
    )


def read(metric, run):
    meta, reader = spec.load_reader(os.path.join(ROOT, "benchmark"), metric)
    return reader(run, meta)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_recorded.json")) as f:
        return json.load(f)["trace"]


def test_on_one_device_the_per_chip_readers_read_what_the_accepted_ones_read(
        recorded):
    run = run_of(recorded, GRID[0], 3000)
    assert run["trace"]["devices"] == 1 and run["trace"]["kernel_events"] > 0
    assert read("sharded_kernel_us_per_op.sat", run) == pytest.approx(
        read("kernel_us_per_op.sat", run), rel=1e-12)
    assert read("sharded_match_kernel_roofline.sat", run) == pytest.approx(
        read("match_kernel_roofline.sat", run), rel=1e-12)
    assert 0 < read("sharded_match_kernel_roofline.sat", run)


def test_four_chips_that_each_do_what_one_did_read_the_one_devices_numbers(
        recorded):
    """Four devices, each running the recorded device's ops on a quarter of a
    grid four times as wide that carries four times the ops: a chip's kernel
    time per op and its share of the roofline are the one device's. The
    accepted readers misread that trace by D and D x D."""
    one = run_of(recorded, GRID[0], 3000)
    four_trace = copy.deepcopy(recorded)
    four_trace["devices"] = [copy.deepcopy(recorded["devices"][0])
                             for _ in range(4)]
    four = run_of(four_trace, 4 * GRID[0], 4 * 3000)
    assert four["trace"]["devices"] == 4
    assert four["trace"]["kernel_events"] == 4 * one["trace"]["kernel_events"]
    for metric, accepted in (
            ("sharded_kernel_us_per_op.sat", "kernel_us_per_op.sat"),
            ("sharded_match_kernel_roofline.sat", "match_kernel_roofline.sat")):
        assert read(metric, four) == pytest.approx(read(accepted, one),
                                                   rel=1e-9), metric
    assert read("kernel_us_per_op.sat", four) == pytest.approx(
        read("kernel_us_per_op.sat", one) / 16, rel=1e-9)  # D x D too low
    assert read("match_kernel_roofline.sat", four) > 3.9 * read(
        "match_kernel_roofline.sat", one)  # too high: D events on D x the rows


def test_rows_per_frame_counts_global_rows_over_frames_committed(recorded):
    run = run_of(recorded, 2048, 3000, n_grids=8, frames=4)
    run["grids"][1::2] = [(g[0], 32, 512, 256, 400) for g in run["grids"][1::2]]
    assert read("grid_rows_per_frame.sat", run) == (4 * 2048 + 4 * 32) / 4
    run["grids"] = []  # a run that noted no grid reads nothing
    assert read("grid_rows_per_frame.sat", run) is None


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the span or a run without a trace (the parent, an
    untraced or CPU run): nothing, and no exception."""
    bare = dict(trace=None, rehearsal=True, device_kind="cpu", grids=[],
                cell=dict(config=dict(service=dict(engine=ENGINE))),
                win=dict(t0_ns=0, t1_ns=1, c0={}, c1={}))
    for metric in ("shard_put_ms_per_grid.sat", "grid_rows_per_frame.sat",
                   "sharded_kernel_us_per_op.sat",
                   "sharded_match_kernel_roofline.sat"):
        assert read(metric, bare) is None, metric
    spans = dict(trace=dict(spans={"grid_dispatch": [4, 0.02]}, window_s=1.0))
    assert read("shard_put_ms_per_grid.sat", dict(bare, **spans)) is None
    spans["trace"]["spans"]["shard_put"] = [4, 0.002]
    assert read("shard_put_ms_per_grid.sat", dict(bare, **spans)) == \
        pytest.approx(0.5)

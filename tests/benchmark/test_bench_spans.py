"""The per-layer metrics that read the program's leaf spans (PR 25): the
span_share reader on the recorded chip trace, the twenty entries against
their files, and a traced rehearsal of every cell naming the span_ms metrics
a chip run would report."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import spec, tracered

ROOT = spec.ROOT
BASE = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}

#: metric -> (reader, span, unit)
SPAN_METRICS = {
    "admit_ms_per_request": ("span_ms", "gateway_admit", "ms/request"),
    "frame_admit_ms_per_frame": ("span_ms", "frame_admit", "ms/frame"),
    "pack_ms_per_frame": ("span_ms", "frame_pack", "ms/frame"),
    "dispatch_ms_per_grid": ("span_ms", "grid_dispatch", "ms/grid"),
    "fetch_ms_per_frame": ("span_ms", "frame_fetch", "ms/frame"),
    "decode_ms_per_frame": ("span_ms", "frame_decode", "ms/frame"),
    "feed_fanout_ms_per_frame": ("span_ms", "feed_fanout", "ms/frame"),
    "consumer_wait_share": ("span_share", "consumer_poll", "share"),
    "feed_wait_share": ("span_share", "feed_poll", "share"),
    "stream_wait_share": ("span_share", "stream_wait", "share"),
}
CELLS = {"sat": (["spot10k.sat", "hotpair8.sat"], "orders_per_s"),
         "paced": (["spot10k.paced", "hotpair8.paced"], "fill_latency_p50_ms")}


def _read(reader, run, meta):
    return spec.load_module(
        "reader_" + reader, os.path.join(BASE, "readers", reader + ".py")
    ).read(run, meta)


def test_span_share_on_the_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_recorded.json")) as f:
        doc = json.load(f)
    reduced = tracered.reduce(doc["trace"])
    count, seconds = reduced["spans"]["pipeline_feed"]
    share = _read("span_share", dict(trace=reduced),
                  dict(span="pipeline_feed"))
    assert share == pytest.approx(seconds / reduced["window_s"])
    assert 0.0 < share < 1.0 and count > 0
    # a span the trace lacks, and a run without a trace, read nothing
    assert _read("span_share", dict(trace=reduced),
                 dict(span="stream_wait")) is None
    # ... unless the program opens it only while it waits and a span it
    # always opens is there: the thread never waited, and the share is 0
    assert _read("span_share", dict(trace=reduced),
                 dict(span="stream_wait", zero_if_seen="pipeline_feed")) == 0.0
    assert _read("span_share", dict(trace=reduced),
                 dict(span="stream_wait", zero_if_seen="feed_fanout")) is None
    assert _read("span_share", dict(trace=None),
                 dict(span="pipeline_feed")) is None


def test_span_share_reads_nothing_where_the_trace_has_no_device_plane():
    # a CPU rehearsal: host spans, no /device:TPU plane, window_s 0
    reduced = tracered.reduce(dict(
        devices=[], host=[["consumer_poll", 10, 2_000_000]], lines=[]))
    assert reduced["window_s"] == 0.0
    assert reduced["spans"]["consumer_poll"] == [1, pytest.approx(0.002)]
    assert _read("span_share", dict(trace=reduced),
                 dict(span="consumer_poll")) is None
    assert _read("span_ms", dict(trace=reduced),
                 dict(span="consumer_poll")) == pytest.approx(2.0)


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_a_span_metrics_entry_and_file_agree(name, kind):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader, span, unit = SPAN_METRICS[name]
    cells, moves = CELLS[kind]
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == f"{name}.{kind}"]
    assert entry["workloads"] == cells
    assert (entry["unit"], entry["moves"], entry["source"]) == (
        unit, moves, "program_span")
    assert entry["better"] == ("higher" if reader == "span_share"
                               else "lower")
    meta, read = spec.load_reader(BASE, entry["name"])
    assert callable(read)
    assert (meta["reader"], meta["span"]) == (reader, span)
    # stream_wait is open only while the stream waits: its file also names
    # the span whose presence makes an absent wait a share of 0
    seen = [meta["zero_if_seen"]] if "zero_if_seen" in meta else []
    assert meta["spans"] == [span] + seen
    assert seen == (["feed_fanout"] if span == "stream_wait" else [])
    assert (meta["layer"], meta["unit"], meta["moves"]) == (
        entry["layer"], unit, moves)
    # the span enters the idle gaps' names through the cell's span list
    for cell in cells:
        per_layer = [m["name"] for m in spec.load_cell(cell)["per_layer"]]
        assert span in spec.span_names(BASE, per_layer)


@pytest.fixture(scope="module")
def traced_rehearsals(linked_root, finish):
    """A traced rehearsal of every cell, started side by side, in a root of
    their own (another file's rehearsals of the same cells run beside
    them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    root = linked_root("traced")
    procs = {
        cell: subprocess.Popen(
            [sys.executable, os.path.join(BASE, "run.py"), "--workload", cell,
             "--seed", "2147483777", "--seconds", "3", "--trace", "1",
             "--rehearsal", "--root", root],
            cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for cell in cells
    }
    out = {}
    for cell, p in procs.items():
        line, _lines, stderr = finish(cell, p)
        out[cell] = (line, stderr)
    return out


@pytest.mark.parametrize("cell", ["spot10k.sat", "hotpair8.sat",
                                  "spot10k.paced", "hotpair8.paced"])
def test_a_traced_rehearsal_names_the_cells_span_metrics(cell,
                                                         traced_rehearsals):
    line, stderr = traced_rehearsals[cell]
    assert line["correct"] is True
    reported = set(line["metrics_that_a_chip_run_would_report"])
    kind = cell.rsplit(".", 1)[1]
    assert f"feed_ms_per_frame.{kind}" in reported
    for name, (reader, _span, _unit) in SPAN_METRICS.items():
        # span_share needs the device plane's window: not on the CPU
        assert (f"{name}.{kind}" in reported) == (reader == "span_ms"), name
    # the service's baseline line comes through on the run's stderr
    assert "gome_tpu.tracing: span baseline:" in stderr

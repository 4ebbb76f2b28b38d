"""`spot10k_durable` and its cell `spot10k_durable.sat` (PR 36): spot10k's venue
on the file bus with snapshots, killed and booted again on its directory. The
configuration is spot10k's but for the bus, the persister and the restart; its
stream is spot10k's byte for byte; the committed cell's rehearsal is `correct`
with the five `restart.*` numbers at 0 and cuts taken before the kill; and a
rehearsal whose match-feed cursor is committed at queue time, or whose match
log loses its tail before the second boot, is not `correct` on a `restart.*`
number.

The faults are injected from here, into the serving process alone, through a
`sitecustomize` on the rehearsal's PYTHONPATH: neither the program nor the
harness knows them. On the CPU a toy frame's events reach the subscriber in a
millisecond, so a kill finds nothing on its way and a cursor that ran ahead
loses nothing; the chip's cell stands at the SubscribeMatches stream, where
events do wait. The faulted rehearsals, and the sound one they are read
against (`held`), therefore hold every chunk 0.8 s in the stream's handler and
every acknowledgement 0.3 s (a slow disk), so that at the kill two requests'
events are below a snapshot's `match_end` and not yet handed over."""

import hashlib
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmark import spec, stream

from test_bench_stream import PARENT_DIGESTS

ROOT = spec.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")
CELL = "spot10k_durable.sat"
RESTART = ("restart.events_mismatched", "restart.events_missing",
           "restart.events_extra", "restart.books_mismatched",
           "restart.not_recovered")

SITECUSTOMIZE = textwrap.dedent('''
    """Faults for tests/benchmark/test_bench_durable_cell.py, in the serving
    process only (benchmark/serve.py <args as JSON>)."""
    import json, os, sys, time

    fault = os.environ.get("DURABLE_CELL_TEST_FAULT", "")
    argv = list(getattr(sys, "orig_argv", []))
    if fault and any(a.endswith("serve.py") for a in argv):
        args = json.loads(argv[-1])
        if args.get("resumed"):
            if fault == "tail":
                # the match log loses its last four records before the boot
                path = os.path.join(args["run_dir"], "bus_data",
                                    "matchOrder.log")
                data, ends, pos = open(path, "rb").read(), [0], 0
                while pos + 4 <= len(data):
                    pos += 4 + int.from_bytes(data[pos:pos + 4], "big")
                    ends.append(pos)
                with open(path, "rb+") as f:
                    f.truncate(ends[-5])
        else:
            serve = next(a for a in argv if a.endswith("serve.py"))
            sys.path.insert(0, os.path.dirname(os.path.dirname(
                os.path.abspath(serve))))  # as serve.py itself does, later
            from gome_tpu.service import gateway, matchfeed

            def held(method):
                def get(self, *a, **kw):
                    chunk = method(self, *a, **kw)
                    time.sleep(0.8)  # taken up, not yet handed to gRPC
                    return chunk
                return get

            sub = matchfeed._Subscription
            sub.get, sub.get_nowait = held(sub.get), held(sub.get_nowait)
            publish = gateway.OrderGateway._publish

            def slow_publish(self, body, **kw):
                publish(self, body, **kw)
                time.sleep(0.3)  # the acknowledgement waits for a slow disk

            gateway.OrderGateway._publish = slow_publish
            if fault == "cursor":
                def at_queue_time(self):  # the parent's cursor
                    if self._next > self._committed:
                        self.bus.match_queue.commit(self._next)
                        self._committed = self._next

                matchfeed.MatchFeed._commit_handed = at_queue_time
''')

RUNS = {"plain": "", "held": "held", "cursor": "cursor", "tail": "tail"}


def config_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_spot10ks_on_the_file_bus_with_snapshots():
    durable, one = config_of("spot10k_durable"), config_of("spot10k")
    for key in ("flow", "log_level", "scan_giveways_allowed"):
        assert durable[key] == one[key], key
    assert durable["rehearsal"]["flow"] == one["rehearsal"]["flow"]
    service = durable["service"]
    assert service["engine"] == one["service"]["engine"]
    assert service["grpc"] == one["service"]["grpc"]
    assert service["bus"] == {"backend": "file", "dir": "bus_data",
                              "match_wire": "frame"}
    assert service["persist"] == {"enabled": True, "dir": "snapshots",
                                  "every_n_batches": 8, "keep": 2}
    assert durable["restart"] == {"after": "window", "requests": 4,
                                  "timeout_s": 180}
    assert durable["reduced"] == [] and len(durable["source"]) <= 200
    assert durable["assumed"][:len(one["assumed"])] == one["assumed"]
    guarantees = durable["guarantees"]
    assert [g for i, g in enumerate(guarantees[:5]) if i != 1] == [
        g for i, g in enumerate(one["guarantees"]) if i != 1]
    assert "memory bus" not in " ".join(guarantees)
    assert "survives the death of the serving process" in guarantees[5]
    assert "cut every 8 committed frames" in guarantees[6]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["configs"][-1]
    assert entry["name"] == "spot10k_durable" and entry["reduced"] == []
    assert entry["source"] == durable["source"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="spot10k_durable",
        traffic="sat", chips=1)
    cell = spec.load_cell(CELL)
    assert cell["traffic"] == spec.load_cell("spot10k.sat")["traffic"]
    assert [m["name"] for m in cell["end_to_end"]] == ["orders_per_s",
                                                       "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 15 and names[-4:] == [
        "order_log_append_ms_per_request.sat",
        "match_log_append_ms_per_frame.sat", "snapshot_cut_ms.sat",
        "snapshot_write_share.sat"]
    for name in names[-4:]:
        meta, _read = spec.load_reader(cell["base"], name)
        assert meta["span"] == meta["spans"][0] and len(meta["spans"]) == 11
    # the plain reference imports nothing of the program
    with open(os.path.join(ROOT, durable["reference"])) as f:
        assert "gome_tpu" not in f.read()


@pytest.mark.parametrize(
    "case", sorted(c for c in PARENT_DIGESTS if c[0] == "spot10k"), ids=str)
def test_the_stream_has_spot10ks_digests(case):
    _venue, rehearsal, request_orders, n_requests, seed = case
    config = config_of("spot10k_durable")
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


@pytest.fixture(scope="module")
def rehearsals(linked_root, finish, tmp_path_factory):
    """The committed cell rehearsed four times, one after another, each in a
    root of its own: as it is (`plain`), and with the stream and the
    acknowledgements held back (module docstring) soundly (`held`), with the
    feed's cursor committed at queue time (`cursor`), and with the match
    log's tail dropped before the second boot (`tail`)."""
    site = tmp_path_factory.mktemp("site")
    (site / "sitecustomize.py").write_text(SITECUSTOMIZE)
    base = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    path = os.pathsep.join(
        [str(site)] + [p for p in [base.get("PYTHONPATH")] if p])
    out = {}
    for key, fault in RUNS.items():  # one at a time: a rehearsal is seven
        # processes, and the other workers' wall-clock gates run beside it
        p = subprocess.Popen(
            [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
             "--seconds", "3", "--trace", "0", "--rehearsal", "--root",
             linked_root("durable_" + key)],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
            env=dict(base, PYTHONPATH=path, DURABLE_CELL_TEST_FAULT=fault))
        out[key] = finish(key, p, timeout=420)
    return out


def compared(result):
    return {name: entry["value"] for name, entry in result["compared"].items()}


@pytest.mark.parametrize("key", ["plain", "held"])
def test_the_rehearsed_cell_is_correct_with_every_restart_number_at_0(
        key, rehearsals):
    result, lines, stderr = rehearsals[key]
    numbers = compared(result)
    assert result["correct"] is True and result["failed"] == 0, numbers
    assert {name: numbers[name] for name in RESTART} == dict.fromkeys(
        RESTART, 0)
    assert all(v == 0 for v in numbers.values())
    assert any("restart: SIGKILL at the acknowledgement of request" in ln
               for ln in lines)
    # the boot names the snapshot it took: snap-<n>, so the first process
    # had cut n + 1 times before the kill, with frames in flight or not
    took = re.findall(r"recovery: snapshot=snap-(\d+), (\d+) bytes restored "
                      r"in [0-9.]+ s; (\d+) frames", stderr)
    assert took, stderr[-2000:]
    snap, restored_bytes, frames = map(int, took[-1])
    assert snap >= 1 and restored_bytes > 0
    # at most the cadence's frames (2 at rehearsal size), the four requests
    # before the kill and what was in flight
    assert frames <= 2 + 4 + 4


@pytest.mark.parametrize("key", ["cursor", "tail"])
def test_a_cursor_ahead_of_the_hand_over_or_a_lost_match_tail_is_not_correct(
        key, rehearsals):
    result, lines, _stderr = rehearsals[key]
    numbers = compared(result)
    assert result["correct"] is False
    broken = {name for name in RESTART if numbers[name] != 0}
    assert broken, numbers
    # the window itself was sound: only the restart's numbers say so
    assert all(v == 0 for name, v in numbers.items() if name not in RESTART)
    failed = [ln for ln in lines if ln.endswith("FAIL")]
    assert failed and all(" restart." in ln for ln in failed)

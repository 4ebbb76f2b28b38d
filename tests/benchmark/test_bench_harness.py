"""The harness end to end on the CPU rehearsal (toy sizes, kernel interpreted):
the result line's keys, `correct` turning false when the timed path is broken
underneath, cells found by name from new files alone, and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import spec

ROOT = spec.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}


def run(*args, cwd=ROOT, script=RUN, timeout=240):
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


REHEARSALS = {
    "sat": ("hotpair8.sat", "2", "--trace", "0"),
    "paced_traced": ("spot10k.paced", "3", "--trace", "1"),
    "price": ("hotpair8.sat", "2", "--trace", "0", "--sabotage", "price"),
    "seq": ("hotpair8.sat", "2", "--trace", "0", "--sabotage", "seq"),
    "control": ("spot10k.sat", "2", "--trace", "0", "--control"),
}


@pytest.fixture(scope="module")
def rehearsals(linked_root, finish):
    """Every rehearsal this file reads, started side by side, each in a root
    of its own. Something is left in the first one's run directory, as an
    earlier run of the cell would leave it."""
    roots = {key: linked_root(key) for key in REHEARSALS}
    stale = os.path.join(roots["sat"], ".bench_run", "hotpair8.sat", "trace")
    os.makedirs(stale)
    with open(os.path.join(stale, "left_by_an_earlier_run"), "w") as f:
        f.write("x")
    procs = {
        key: subprocess.Popen(
            [sys.executable, RUN, "--workload", wl, "--seed", "2147483659",
             "--seconds", seconds, "--rehearsal", "--root", roots[key], *more],
            cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for key, (wl, seconds, *more) in REHEARSALS.items()
    }
    out = {}
    for key, p in procs.items():
        result, lines, stderr = finish(key, p)
        assert all(ln.startswith("[CPU REHEARSAL") for ln in lines[:-1]), key
        out[key] = (result, lines)
        out[key + ".stderr"] = stderr
    out["roots"] = roots
    return out


@pytest.fixture(scope="module")
def sat(rehearsals):
    return rehearsals["sat"]


def test_a_rehearsal_is_labelled_and_carries_no_number_under_a_metrics_name(sat):
    out, lines = sat
    assert list(out) == ["cpu_rehearsal", "correct", "attempted", "failed",
                         "metrics_that_a_chip_run_would_report", "device",
                         "compared"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics_that_a_chip_run_would_report"] == [
        "orders_per_s", "setup_s"]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_every_number_compared_is_printed_beside_its_limit(sat):
    _out, lines = sat
    compared = [ln for ln in lines if "] compare hotpair8 " in ln]
    assert len(compared) >= 15
    assert all("(limit 0) ok" in ln for ln in compared)
    names = {ln.split("compare hotpair8 ")[1].split(" = ")[0] for ln in compared}
    assert {"events.mismatched", "events.missing", "events.extra",
            "matchfeed.gaps", "matchfeed.dupes", "books.invariant_failures",
            "kernel.no_compiled_pallas_grid", "consumer.step_failures",
            "consumer.poison_orders"} <= names


def test_the_numbers_compared_end_the_result_line_and_standard_error(
        sat, rehearsals):
    out, lines = sat
    printed = {ln.split("compare hotpair8 ")[1].split(" = ")[0]: ln
               for ln in lines if "] compare hotpair8 " in ln}
    assert list(out["compared"]) == list(printed)
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    last = rehearsals["sat.stderr"].strip().splitlines()[-len(printed):]
    assert [ln.split("compared ")[1] for ln in last] == [
        f"{name} = 0 (limit 0)" for name in printed]


def test_a_run_empties_its_directory_before_the_serving_process_boots(
        sat, rehearsals):
    """What an earlier run of the cell left there is gone, and what this run
    kept on disk lies under it."""
    _out, lines = sat
    run_dir = os.path.join(rehearsals["roots"]["sat"], ".bench_run",
                           "hotpair8.sat")
    assert any(f"run directory {run_dir}: emptied" in ln for ln in lines)
    assert os.listdir(run_dir) == ["config.yaml"]
    at = [i for i, ln in enumerate(lines)
          if "emptied" in ln or "serving ready" in ln]
    assert len(at) == 2 and "emptied" in lines[at[0]]


def test_the_report_line_carries_the_witnesses(sat):
    _out, lines = sat
    report = json.loads(
        next(ln for ln in lines if "] report " in ln).split("] report ")[1])
    for key in ("orders_complete_per_second", "events_per_order",
                "device_calls_per_frame", "lanes_by_class_start",
                "steered_depth_start_mid_end", "rewinds", "gc_serving"):
        assert key in report, key
    assert any("pinning" in ln for ln in lines)
    assert report["rewinds"]["fallbacks"] == 0


def test_the_result_line_of_a_chip_run_has_exactly_the_contracts_keys():
    sys.path.insert(0, ROOT)
    from benchmark.run import result_line

    metrics = {"orders_per_s": dict(value=1.5, unit="orders/s")}
    device = dict(platform="tpu", kind="TPU v5 lite", count=1,
                  memory_peak_bytes=7)
    out = result_line(False, True, 10, 0, metrics, device, None)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device"]
    traced = result_line(False, True, 10, 0, metrics,
                         dict(device, busy_s=1.0, window_s=2.0),
                         dict(device_ops=[], idle_gaps=[]),
                         {"events.missing": 2})
    assert list(traced) == list(out) + ["breakdown", "compared"]
    assert traced["compared"] == {"events.missing": dict(value=2, limit=0)}


def test_a_traced_paced_rehearsal_names_the_cells_per_layer_metrics(rehearsals):
    out, lines = rehearsals["paced_traced"]
    assert out["correct"] is True
    reported = set(out["metrics_that_a_chip_run_would_report"])
    assert {"gen_late_p99_ms.paced", "fill_latency_p95_ms.paced",
            "admit_us_per_order.paced",
            "device_calls_per_frame.paced", "fanout_us_per_event.paced",
            "rewinds_in_window.paced"} <= reported
    assert not any(name.endswith(".sat") for name in reported)


@pytest.mark.parametrize("kind, number", [
    ("price", "events.mismatched"), ("seq", "events.missing")])
def test_correct_turns_false_when_the_timed_path_is_broken(kind, number,
                                                           rehearsals):
    out, lines = rehearsals[kind]
    assert out["correct"] is False
    # a whole result all the same: the contract's counts, whatever broke
    assert out["attempted"] >= 1 and 0 <= out["failed"] <= out["attempted"]
    failed = [ln for ln in lines if ln.endswith("FAIL")]
    assert any(f" {number} = " in ln for ln in failed), failed
    if kind == "seq":
        assert any(" matchfeed.gaps = " in ln for ln in failed)
        # the frame is lost in warm-up: a stall there fails the run by itself
        assert any(" window.warmup_stalled = 1 " in ln for ln in failed)


def test_the_control_is_printed_and_comes_out_not_correct(rehearsals):
    out, lines = rehearsals["control"]
    assert out["correct"] is True
    assert any("control_correct False (has to be False)" in ln for ln in lines)


def test_a_cell_a_mix_a_config_and_a_metric_are_found_from_new_files(tmp_path):
    """A later PR adds a venue, a mix, a cell and a per-layer metric by adding
    files and entries: nothing of the harness is edited."""
    root = str(tmp_path)
    base = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(base, "configs", "hotpair8.json")) as f:
        venue = json.load(f)
    venue["name"] = "hotpair2"
    venue["flow"]["symbols"] = 2
    with open(os.path.join(base, "configs", "hotpair2.json"), "w") as f:
        json.dump(venue, f)
    with open(os.path.join(base, "traffic", "sat.json")) as f:
        mix = json.load(f)
    mix["outstanding"] = 2
    with open(os.path.join(base, "traffic", "sat2.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(base, "metrics", "frames_in_window.sat.json"),
              "w") as f:
        json.dump(dict(reader="delta", counter="frames",
                       layer="bus (bus/, order queue)", unit="frames",
                       moves="orders_per_s"), f)
    bench["configs"].append(dict(name="hotpair2", source="test",
                                 file="benchmark/configs/hotpair2.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="hotpair2.sat2", config="hotpair2",
                                   traffic="sat2", chips=1, why="test"))
    bench["end_to_end"][0]["workloads"].append("hotpair2.sat2")
    bench["per_layer"].append(dict(
        name="frames_in_window.sat", unit="frames", better="higher",
        source="program_counter", layer="bus (bus/, order queue)",
        moves="orders_per_s", workloads=["hotpair2.sat2"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("hotpair2.sat2", root)
    assert cell["config"]["flow"]["symbols"] == 2
    assert cell["traffic"]["outstanding"] == 2
    assert [m["name"] for m in cell["per_layer"]] == ["frames_in_window.sat"]
    meta, read = spec.load_reader(cell["base"], "frames_in_window.sat")
    assert read(dict(win=dict(c0=dict(frames=3), c1=dict(frames=10))),
                meta) == 7
    # the same harness runs it: the copy is driven against this checkout
    p = run("--workload", "hotpair2.sat2", "--seed", "9", "--seconds", "2",
            "--trace", "1", "--rehearsal", "--root", root)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert "frames_in_window.sat" in out["metrics_that_a_chip_run_would_report"]


def test_no_file_of_the_harness_tests_a_cells_or_a_configurations_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {w["name"] for w in bench["workloads"]} | \
        {c["name"] for c in bench["configs"]}
    folder = os.path.join(ROOT, "benchmark")
    for fn in os.listdir(folder):
        if fn.endswith(".py"):
            with open(os.path.join(folder, fn)) as f:
                text = f.read()
            for name in names:
                assert f'"{name}"' not in text and f"'{name}'" not in text, \
                    (fn, name)


def test_every_per_layer_metric_has_its_file_and_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        meta, read = spec.load_reader(os.path.join(ROOT, "benchmark"),
                                      m["name"])
        assert callable(read)
        assert (meta["layer"], meta["unit"], meta["moves"]) == (
            m["layer"], m["unit"], m["moves"]), m["name"]
        assert m["moves"] in e2e


def test_a_run_refuses_the_cpu_without_the_rehearsal_flag():
    p = run("--workload", "hotpair8.sat", "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 3
    assert p.stdout.strip().splitlines()[-1][:1] != "{"
    assert "TPU" in p.stderr


def test_the_benchmark_alone_in_a_directory_exits_nonzero_and_prints_no_result(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    p = run("--workload", "hotpair8.sat", "--seed", "1", "--seconds", "1",
            "--trace", "0", cwd=root,
            script=os.path.join(root, "benchmark", "run.py"))
    assert p.returncode == 2
    assert p.stdout.strip() == ""

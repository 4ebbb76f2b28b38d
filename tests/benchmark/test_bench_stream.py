"""The generator: stratified (the same work per request in every seed), depth
steered inside each lane's band, the same bytes from the same seed and from any
number of workers, and the control that the comparison has to fail."""

import json
import multiprocessing
import os

import numpy as np
import pytest

from benchmark import compare, reference, spec, stream

R = 256
SEEDS = [1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 2147483659, 1873402117]


def flow_of(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        doc = json.load(f)
    flow = doc["flow"]
    for key, value in doc["rehearsal"]["flow"].items():
        flow[key] = {**flow[key], **value} if isinstance(value, dict) else value
    return flow


@pytest.fixture(scope="module")
def streams():
    flow = flow_of("hotpair8")
    return flow, {s: stream.generate(flow, s, 40, R) for s in SEEDS}


def per_request(values, n_requests, minlength):
    return np.stack([np.bincount(values[k * R:(k + 1) * R], minlength=minlength)
                     for k in range(n_requests)])


def test_every_request_has_the_same_count_per_rank_in_every_seed(streams):
    flow, made = streams
    tables = [per_request(m["ranks"], 40, flow["symbols"])
              for m in made.values()]
    for t in tables[1:]:
        assert (t == tables[0]).all()
    # and after the listing the counts follow Zipf(1)
    k0 = -(-len(stream.listing_plan(flow)) // R)
    share = tables[0][k0:, 0].sum() / ((40 - k0) * R)
    assert abs(share - stream.popularity(flow)[0]) < 0.005


def test_every_request_has_the_same_count_per_kind_in_every_seed(streams):
    _flow, made = streams

    def kinds(m):
        c = m["cols"]
        code = np.where(c["cancel"], 2, c["kind"]).astype(np.int64)
        return per_request(code, 40, 3)

    tables = [kinds(m) for m in made.values()]
    for t in tables[1:]:
        assert (t == tables[0]).all()
    want = stream.kind_shares(_flow) * R
    k0 = -(-len(stream.listing_plan(_flow)) // R)
    assert abs(tables[0][k0:, 2].mean() - (want[2] + want[3])) < 2


def test_the_listing_puts_every_lane_inside_its_band(streams):
    flow, made = streams
    plan = stream.listing_plan(flow)
    assert (np.bincount(plan) == np.bincount(plan)[0]).all()  # all lanes alike
    k0 = -(-len(plan) // R)
    for m in made.values():
        assert (m["ranks"][:len(plan)] == plan).all()
        assert not m["cols"]["cancel"][:len(plan)].any()
        assert (m["events"][:, 0] >= len(plan)).all()  # quotes only: no event
        for rank, tr in m["traces"].items():
            lo, hi = stream.band_of(flow, rank)
            assert tr[k0:, [0, 3]].min() >= lo and tr[k0:, [1, 4]].max() <= hi


def test_the_seed_draws_prices_sides_users_and_the_order_inside_a_request(streams):
    _flow, made = streams
    a, b = made[1]["cols"], made[2]["cols"]
    tail = slice(20 * R, None)  # after the opening, which is the same
    for col in ("price", "side", "uid", "sym"):
        assert (a[col][tail] != b[col][tail]).any(), col


def test_the_opening_is_the_same_work_in_every_run(streams):
    flow, made = streams
    n_open = stream.opening_requests(flow, R) * R
    a, b = made[1], made[2]
    assert (a["ranks"][:n_open] == b["ranks"][:n_open]).all()
    for col in ("uid", "oid", "side", "kind", "cancel", "price", "volume"):
        assert (a["cols"][col][:n_open] == b["cols"][col][:n_open]).all(), col


def test_every_steered_lane_stays_inside_its_band_once_it_is_in(streams):
    flow, made = streams
    for seed, m in made.items():
        assert sorted(m["traces"]) == stream.traced_ranks(flow)
        for rank, tr in m["traces"].items():
            lo, hi = stream.band_of(flow, rank)
            inside = np.flatnonzero((tr[:, 0] >= lo) & (tr[:, 3] >= lo))
            assert len(inside), (seed, rank)
            after = tr[inside[0]:]
            assert after[:, [0, 3]].min() >= lo, (seed, rank)
            assert after[:, [1, 4]].max() <= hi, (seed, rank)
            assert inside[0] <= 4, (seed, rank, inside[0])


def test_the_tail_of_spot10k_stays_under_its_band():
    flow = flow_of("spot10k")
    m = stream.generate(flow, 7, 60, R)
    cols, ev = m["cols"], m["events"]
    # replay: the resting count of every lane after every order
    books = {}
    hi = {r: stream.band_of(flow, r)[1] for r in range(flow["symbols"])}
    rank_of_sym = np.argsort(m["sym_of_rank"])
    sink = [].append
    for i in range(len(cols["sym"])):
        s = int(cols["sym"][i])
        b = books.setdefault(s, reference.Book())
        if cols["cancel"][i]:
            b.cancel(i, s, int(cols["uid"][i]), int(cols["oid"][i]),
                     int(cols["side"][i]), int(cols["price"][i]), sink)
        else:
            b.add(i, s, int(cols["uid"][i]), int(cols["oid"][i]),
                  int(cols["side"][i]), int(cols["kind"][i]),
                  int(cols["price"][i]), int(cols["volume"][i]), sink)
        assert max(b.count) <= hi[int(rank_of_sym[s])]
    assert len(ev) > 0


def test_the_same_seed_gives_the_same_bytes(streams):
    flow, made = streams
    again = stream.generate(flow, 5, 40, R)
    for col in stream.COLUMNS:
        assert again["cols"][col].tobytes() == made[5]["cols"][col].tobytes()
    assert again["events"].tobytes() == made[5]["events"].tobytes()
    other = stream.generate(flow, 8, 40, R)
    assert other["cols"]["price"].tobytes() != again["cols"]["price"].tobytes()


def test_merged_worker_output_equals_single_process_output(streams):
    flow, made = streams
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(3) as pool:
        split = stream.generate(flow, 13, 40, R, workers=3, pool=pool)
    for col in stream.COLUMNS:
        assert (split["cols"][col] == made[13]["cols"][col]).all(), col
    assert (split["events"] == made[13]["events"]).all()
    for rank, tr in made[13]["traces"].items():
        assert (split["traces"][rank] == tr).all()


def test_the_streams_events_are_the_references_own(streams):
    _flow, made = streams
    cols = {k: v.tolist() for k, v in made[21]["cols"].items()}
    replay = np.array(reference.run(cols), np.int64)
    assert (replay == made[21]["events"]).all()


def test_no_order_fills_more_makers_than_the_engines_record_holds(streams):
    _flow, made = streams
    for m in made.values():
        ev = m["events"]
        fills = ev[ev[:, 12] > 0][:, 0]
        assert np.bincount(fills - fills.min()).max() <= 16


@pytest.mark.parametrize("seed", [3, 34, 2147483659])
def test_the_control_comes_out_not_correct(seed, streams):
    """The reference with time priority reversed inside a level, put in the
    program's place: the comparison has to fail it (limit 0)."""
    _flow, made = streams
    m = made[seed]
    n = 40 * R
    sound = compare.expected_rows(m["events"], n)
    assert compare.compare_events(sound, sound)["events.mismatched"] == 0
    broken = compare.control(m["cols"], n, sound, "lifo")
    assert broken["events.mismatched"] > 100


#: sha256 over the columns (stream.COLUMNS order) and the events of streams
#: made by the parent commit's generator (ba9ae75, before a configuration
#: could bring its own Book and add kinds; PR 27's builder ran the function
#: below on an unpacked `git archive` of it): venue, rehearsal or the cell's
#: own flow, request_orders, requests, seed. The accepted venues' streams may
#: not move by a byte.
PARENT_DIGESTS = {
    ("spot10k", True, 128, 40, 2):
        "f046f6a63b8a675653214df642907225151eecbb0f2fbe4e64feef4d3be16cbf",
    ("spot10k", True, 128, 40, 2147483659):
        "66900f3214b681254a27a564a5a4a314fbb9894d51b1502d6e61a0338debb478",
    ("spot10k", False, 4096, 24, 1873402117):
        "5fa656fc4229b97feb2a90e8a78190e5567938c0c0beb9e708708cfa776602ef",
    ("spot10k", False, 62, 1400, 771203945): 
        "37999b9c39428518dea6afb9974997b13163614b55135ee2ea7e8bf7c1de233a",
    ("hotpair8", True, 128, 40, 2):
        "d49a922b9424b1052ae0af31b8407ff1e536b2953bf84d39233cdeaad1028973",
    ("hotpair8", True, 128, 40, 2147483659):
        "8c1e11e7abda877f18fc46f393d23feb46fe9d02c34ff0ec30930e4cddbbd9f3",
    ("hotpair8", False, 4096, 12, 1873402117):
        "ad7d0c3061919c3a2ddc4024101744599689f09b94fe0bfa5737bca0e45e3eb6",
}


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("case", sorted(PARENT_DIGESTS), ids=str)
def test_the_accepted_venues_streams_are_the_parents_byte_for_byte(case,
                                                                   workers):
    import hashlib

    venue, rehearsal, request_orders, n_requests, seed = case
    with open(os.path.join(spec.HERE, "configs", venue + ".json")) as f:
        config = json.load(f)
    if rehearsal:
        spec._merge(config, config["rehearsal"])
    made = stream.generate(
        config["flow"], seed, n_requests, request_orders, workers=workers,
        reference_path=os.path.join(spec.ROOT, config["reference"]))
    h = hashlib.sha256()
    for col in stream.COLUMNS:
        h.update(np.ascontiguousarray(made["cols"][col]).tobytes())
    h.update(np.ascontiguousarray(made["events"], dtype=np.int64).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[case]

"""The benchmark's traffic generator and load loops (CPU, no engine)."""

import json
import os
import time

import pytest

from benchmarks.harness import loadgen, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MIXES = sorted(os.listdir(os.path.join(REPO, "benchmarks", "traffic")))


def mix(name):
    with open(os.path.join(REPO, "benchmarks", "traffic", name)) as f:
        return json.load(f)


def gen(name, seed, **kw):
    m = mix(name)
    if m["loop"] == "open":
        kw.setdefault("rate", 3.0)
        kw.setdefault("seconds", 40.0)
    return traffic.Generator(m, 32000, seed, **kw)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = gen(name, 2 ** 31 + 17), gen(name, 2 ** 31 + 17)
    ra, rb = [a.next() for _ in range(40)], [b.next() for _ in range(40)]
    assert [(r.prompt_ids, r.max_tokens, r.sampling_seed, r.due_s)
            for r in ra] == [(r.prompt_ids, r.max_tokens, r.sampling_seed,
                              r.due_s) for r in rb]


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_shape_other_content(name):
    """A seed changes token ids and sampling seeds, never how much work
    arrives when: lengths, their order and the due instants are the
    mix's own layout."""
    a, b = gen(name, 1), gen(name, 2)
    n = a.count
    ra, rb = [a.next() for _ in range(n)], [b.next() for _ in range(n)]
    shape = lambda rs: [(len(r.prompt_ids), r.max_tokens, r.due_s)  # noqa: E731
                        for r in rs]
    assert shape(ra) == shape(rb)
    assert ra[0].prompt_ids != rb[0].prompt_ids
    assert [r.sampling_seed for r in ra] != [r.sampling_seed for r in rb]


@pytest.mark.parametrize("name", MIXES)
def test_the_layout_is_one_order_not_sorted_and_not_the_seeds(name):
    """Lengths are laid out in one fixed order (not ascending, which
    would put every long prompt at the window's end), prompts and
    outputs in orders of their own."""
    g = gen(name, 1)
    rs = [g.next() for _ in range(g.count)]
    lens = [len(r.prompt_ids) for r in rs]
    outs = [r.max_tokens for r in rs]
    assert lens != sorted(lens) and outs != sorted(outs)
    by_len = sorted(range(len(rs)), key=lambda i: (lens[i], i))
    by_out = sorted(range(len(rs)), key=lambda i: (outs[i], i))
    assert by_len != by_out


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_their_clips(name):
    m = mix(name)
    g = gen(name, 5)
    for r in [g.next() for _ in range(g.count)]:
        assert m["prompt_tokens"]["min"] <= len(r.prompt_ids) \
            <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= r.max_tokens \
            <= m["output_tokens"]["max"]
        assert all(3 <= t < 32000 for t in r.prompt_ids)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    g = gen("chat-steady.json", 9, rate=3.0, seconds=40.0)
    reqs = g.all()
    assert len(reqs) == 120
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] <= 40.0
    # another seed has the same schedule, and the gaps are the
    # quantiles of the exponential at the rate
    assert due == [r.due_s for r in gen("chat-steady.json", 10, rate=3.0,
                                        seconds=40.0).all()]
    assert due[-1] == pytest.approx(40.0)
    gaps = sorted(b - a for a, b in zip([0.0] + due, due))
    assert gaps == pytest.approx(sorted(traffic.arrival_gaps(
        {"process": "poisson"}, 3.0, 120)))


def test_lognormal_quantiles_centre_on_the_median():
    q = traffic.quantiles({"dist": "lognormal", "median": 256, "sigma": 0.7,
                           "min": 16, "max": 2048}, 101)
    assert q[50] == 256 and q[0] >= 16 and q[-1] <= 2048
    assert q == sorted(q)


def test_poisson_arrivals_keep_the_rate_and_nothing_else_is_known():
    poisson = traffic.arrival_gaps({"process": "poisson"}, 4.0, 200)
    assert sum(poisson) == pytest.approx(50.0)
    assert max(poisson) > 10 * min(poisson)     # exponential gaps
    with pytest.raises(ValueError):
        traffic.arrival_gaps({"process": "weibull"}, 1.0, 4)


def test_uniform_quantiles_and_a_single_length():
    assert traffic.quantiles({"dist": "uniform", "min": 0, "max": 100},
                             4) == [12, 38, 62, 88]
    assert traffic.quantiles({"dist": "uniform", "min": 6, "max": 6},
                             3) == [6, 6, 6]


def test_unknown_distribution_raises():
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf", "min": 1, "max": 2}, 3)


def test_prefix_sharing_groups_share_heads():
    m = dict(mix("decode-batch.json"),
             prefix_sharing={"groups": 2, "shared_tokens": 16})
    g = traffic.Generator(m, 32000, 3)
    rs = [g.next() for _ in range(6)]
    assert rs[0].prompt_ids[:16] == rs[2].prompt_ids[:16]
    assert rs[0].prompt_ids[:16] != rs[1].prompt_ids[:16]


def test_no_sharing_means_unique_heads():
    g = gen("rag-prefill.json", 3)
    heads = {tuple(g.next().prompt_ids[:3]) for _ in range(64)}
    assert len(heads) == 64


def test_closed_loop_set_repeats_with_new_content():
    g = gen("decode-batch.json", 4)
    first = [g.next() for _ in range(g.count)]
    again = [g.next() for _ in range(g.count)]
    assert sorted(len(r.prompt_ids) for r in first) == sorted(
        len(r.prompt_ids) for r in again)
    assert first[0].uid != again[0].uid
    assert {r.uid for r in first}.isdisjoint({r.uid for r in again})


# ------------------------------------------------------------ load loops


class FakeStream:
    def __init__(self, delay_s, tokens):
        self.t_done = time.monotonic() + delay_s
        self._tokens = tokens
        self.first_token_time = time.monotonic() + delay_s / 2
        self.finish_time = self.t_done

    @property
    def finish_reason(self):
        return "length" if time.monotonic() >= self.t_done else None

    @property
    def token_ids(self):
        return [5] * (self._tokens if self.finish_reason else 1)


def test_open_loop_times_from_the_due_instant():
    reqs = [traffic.Request(i, [3, 4], 4, 1, due_s=0.05 * (i + 1))
            for i in range(4)]

    def slow_submit(req):
        time.sleep(0.08)        # a stall: later submissions run late
        return FakeStream(0.01, req.max_tokens)

    t0 = time.monotonic()
    rows = loadgen.run_open(slow_submit, reqs, t0)
    assert [r.due_t - t0 for r in rows] == pytest.approx(
        [0.05, 0.10, 0.15, 0.20])
    late = [r.send_t - r.due_t for r in rows]
    assert late[0] < 0.02 and late[3] > 0.05     # lateness is reported
    assert all(r.send_t >= r.due_t for r in rows)


def test_open_loop_sends_on_the_due_instant_not_a_sleep_later():
    """A millisecond of lateness decides which engine round a request
    joins, and from there the whole run (PERF.md): the generator yields
    through the last milliseconds, so the typical send is within a
    tenth of a millisecond of due and never before it."""
    reqs = [traffic.Request(i, [3], 2, 1, due_s=0.02 + 0.01 * i)
            for i in range(40)]
    t0 = time.monotonic() + 0.01
    rows = loadgen.run_open(lambda r: FakeStream(0.0, 2), reqs, t0)
    late = sorted(r.send_t - r.due_t for r in rows)
    assert late[0] >= 0.0
    assert late[len(late) // 2] < 0.0005


def test_open_loop_a_refused_request_is_a_row():
    def refuse(req):
        raise RuntimeError("queue full")
    rows = loadgen.run_open(refuse, [traffic.Request(0, [3], 2, 1,
                                                     due_s=0.0)],
                            time.monotonic())
    assert rows[0].error.startswith("RuntimeError") and rows[0].done


def test_closed_loop_keeps_its_clients_busy():
    g = gen("decode-batch.json", 1)
    sent = []

    def submit(req):
        sent.append(req.uid)
        return FakeStream(0.05, req.max_tokens)

    t_end = time.monotonic() + 0.32
    rows = loadgen.run_closed(submit, g.next, 3, t_end)
    assert 12 <= len(rows) <= 21          # ~6 generations of 3 clients
    assert sum(1 for r in rows if not r.done) <= 3
    assert all(r.tokens_in_window is not None for r in rows)
    assert loadgen.drain(rows, time.monotonic() + 2.0)


def test_marks_run_on_their_own_thread_in_order():
    seen = []
    t = time.monotonic()
    m = loadgen.Marks([(t + 0.06, lambda: seen.append("b")),
                       (t + 0.02, lambda: seen.append("a")),
                       (t + 0.08, lambda: 1 / 0)]).start()
    m.finish(timeout=5.0)
    assert seen == ["a", "b"] and len(m.errors) == 1

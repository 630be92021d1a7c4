"""The traffic generator: one seed gives the same requests twice; two
seeds give the same multiset of lengths and of gaps, and the same count;
every block of the schedule carries the whole multiset. With
``order_seed`` in the mix, two seeds also meet the same lengths at the
same due times and differ in the token ids alone; without it the seed
shuffles the order."""

import pytest

from benchmarks.lib import common, traffic

MIXES = ["serve.backlog", "serve.chat"]


def _spec(name):
    return common.load_json(common.BENCH_DIR, "traffic", name + ".json")


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = traffic.schedule(_spec(mix), 2147483999, 50257)
    b = traffic.schedule(_spec(mix), 2147483999, 50257)
    assert a == b


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_multiset(mix):
    spec = _spec(mix)
    a = traffic.schedule(spec, 1, 50257)
    b = traffic.schedule(spec, 2, 50257)
    lens = lambda s: sorted((len(r["prompt"]), r["max_new_tokens"])  # noqa
                            for r in s)
    assert len(a) == len(b) == spec["block"] * spec["blocks"]
    assert lens(a) == lens(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    shape = lambda s: [(len(r["prompt"]), r["max_new_tokens"],     # noqa
                        r["due"]) for r in s]
    assert "order_seed" in spec and shape(a) == shape(b)
    free = {k: v for k, v in spec.items() if k != "order_seed"}
    c, d = traffic.schedule(free, 1, 50257), traffic.schedule(free, 2, 50257)
    assert shape(c) != shape(d) and lens(c) == lens(d) == lens(a)
    n = spec["block"]
    assert lens(a[:n]) == lens(a[n:2 * n]) == lens(b[3 * n:4 * n])
    gaps = lambda s: sorted(round(y["due"] - x["due"], 9)          # noqa
                            for x, y in zip(s[:n - 1], s[1:n]))
    assert a[-1]["due"] == pytest.approx(b[-1]["due"])
    if spec["rate_per_s"]:
        assert a[-1]["due"] == pytest.approx(len(a) / spec["rate_per_s"])
    else:
        assert a[-1]["due"] == 0


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_fit_the_engine(mix):
    spec = _spec(mix)
    for p, o in traffic.length_multiset(spec):
        assert spec["prompt"]["min"] <= p <= spec["prompt"]["max"]
        assert 1 <= o <= spec["output"]["max"]
        assert p + o <= spec["engine"]["max_len"]


PARENT_640 = "24c9b255532516402484cf86b354c55f901c5fc9aea8960ac5c3f150d595f2ee"


@pytest.mark.parametrize("mix", MIXES)
def test_a_longer_schedule_keeps_its_first_requests(mix):
    """40 blocks open with the 640 requests that 10 blocks were, letter
    for letter: a window meets the requests it met before the schedule
    grew. The backlog's are held to the digest of PR 26's schedule."""
    import hashlib
    import json

    spec = _spec(mix)
    assert spec["blocks"] == 40
    full = traffic.schedule(spec, 2147483999, 50257)
    short = traffic.schedule(dict(spec, blocks=10), 2147483999, 50257)
    assert len(short) == 640 and full[:640] == short
    if mix == "serve.backlog":
        assert hashlib.sha256(json.dumps(short).encode()
                              ).hexdigest() == PARENT_640


def test_the_chat_rate_is_the_share_its_why_states():
    """``rate_per_s`` of the chat mix is a share of what the backlog cell
    completes; the cell's ``why`` states the rate, the share and that
    completion rate, and the three agree with the file."""
    import re

    spec = _spec("serve.chat")
    cell = next(w for w in common.benchmark_json()["workloads"]
                if w["traffic"] == "serve.chat")
    m = re.search(r"at ([\d.]+) req/s = ([\d.]+) of the ([\d.]+) req/s",
                  cell["why"])
    assert m, cell["why"]
    rate, share, backlog = map(float, m.groups())
    assert rate == spec["rate_per_s"]
    assert share == spec["rate_share_of_backlog"]
    assert backlog == spec["backlog_req_per_s"]
    assert 0.6 <= share <= 0.8
    assert rate == pytest.approx(share * backlog, abs=0.006)

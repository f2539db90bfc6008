"""The generators: the same seed gives the same requests, another seed the
same sizes and arrivals in another order, and the schedule covers ramp +
window exactly."""
import json
import os

import pytest

import loadgen

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(CHIP, "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_reproduces_from_a_seed():
    t = mix("chat-open")
    a = loadgen.build_requests(t, 2**31 + 5, 50257, 30)
    b = loadgen.build_requests(t, 2**31 + 5, 50257, 30)
    assert a == b
    n = round(t["arrivals"]["rate_per_s"] * (30 + t["ramp_seconds"]))
    assert len(a) == n
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 30 + t["ramp_seconds"]
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))


def test_another_seed_is_the_same_work_in_another_order():
    t = mix("chat-open")
    a = loadgen.build_requests(t, 1, 50257, 30)
    b = loadgen.build_requests(t, 2, 50257, 30)

    def sizes(reqs):
        return sorted((len(r["tokens"]), r["max_new_tokens"]) for r in reqs)

    def gaps(reqs):
        return sorted(round(y["due"] - x["due"], 9)
                      for x, y in zip(reqs, reqs[1:]))
    assert sizes(a) == sizes(b)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    # all gaps but the last (which closes the span) are the same set
    assert sum(abs(x - y) for x, y in zip(gaps(a), gaps(b))) < 1.0


@pytest.mark.parametrize("name", ["chat-open", "decode-closed"])
def test_sizes_keep_to_the_mix(name):
    t = mix(name)
    reqs = loadgen.build_requests(t, 3, 50257, 30)
    flat = reqs if t["loop"] == "open" else [r for c in reqs for r in c]
    p, o = t["prompt_tokens"], t["output_tokens"]
    for r in flat:
        assert p["min"] <= len(r["tokens"]) <= p["max"]
        assert 1 <= r["max_new_tokens"] <= o["max"]
        assert len(r["tokens"]) + r["max_new_tokens"] <= t["total_max"]
        assert all(0 <= tok < 50257 for tok in r["tokens"])
    if t["loop"] == "closed":
        assert len(reqs) == t["clients"]


def test_lognormal_median_and_poisson_mean():
    t = mix("chat-open")
    pairs = loadgen.sizes(t, 1001)
    prompts = sorted(p for p, _ in pairs)
    assert abs(prompts[500] - t["prompt_tokens"]["median"]) <= 1
    gaps = loadgen.arrival_gaps(t, 400, 50.0)
    assert abs(sum(gaps) - 50.0) < 1e-9
    # exponential: the median gap is ln 2 of the mean
    assert abs(sorted(gaps)[200] / (50.0 / 400) - 0.6931) < 0.02


def test_sse_frames_are_parsed_across_chunk_boundaries():
    client = loadgen.LoadClient(1, "m")
    st = loadgen.Stream({"tokens": [1]}, 0.0)
    raw = (b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
           b"2a\r\nevent: token\ndata: {\"token\": 7, \"index\": 0}\n\n\r\n"
           b"2a\r\nevent: token\ndata: {\"token\": 9, \"index\": 1}\n\n\r\n"
           b"30\r\nevent: done\ndata: {\"tokens\": [7, 9], \"count\": 2}\n\n"
           b"\r\n0\r\n\r\n")
    # feed in three pieces that split a frame
    st.buf = b""
    ended = False
    fed = 0
    for cut in (70, 120, len(raw)):
        st.buf += raw[fed:cut]
        fed = cut
        ended = client._parse(st, float(cut))
    assert ended and st.done and st.tokens == [7, 9]
    assert st.times == sorted(st.times) and len(st.times) == 2
    client.close()

"""The closed-loop generator is a function of --seed alone: the same seed
gives the same requests and prompts, another seed the same sizes with other
adapters and tokens, and every bit of a large seed counts."""
import numpy as np
import pytest

from bench import spec, traffic

MIX = spec.cell("qwen3-1.7b.jd1000.decode").traffic
BIG = 2 ** 33 + 12345


def wave(seed, n=3):
    loop = traffic.ClosedLoop(MIX, seed)
    reqs = loop.first_wave() + [loop.send() for _ in range(n)]
    prompts = [traffic.prompt_tokens(seed, r["rid"], r["prompt_len"], 151936)
               for r in reqs]
    return reqs, prompts


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_same_seed_same_requests(seed):
    (a, pa), (b, pb) = wave(seed), wave(seed)
    assert a == b
    for x, y in zip(pa, pb):
        np.testing.assert_array_equal(x, y)


def test_other_seeds_change_content_not_sizes():
    (a, pa), (b, pb) = wave(7), wave(8)
    assert [r["adapter"] for r in a] != [r["adapter"] for r in b]
    assert [(r["prompt_len"], r["output_len"]) for r in a] == \
        [(r["prompt_len"], r["output_len"]) for r in b]
    assert not np.array_equal(pa[0], pb[0])


def test_high_bits_of_the_seed_count():
    (a, pa), (b, pb) = wave(BIG), wave(BIG & 0xFFFFFFFF)
    assert not np.array_equal(pa[0], pb[0])


def test_requests_fit_the_mix():
    reqs, prompts = wave(3, n=64)
    assert len(reqs) == MIX["clients"] + 64
    assert [r["rid"] for r in reqs] == list(range(len(reqs)))
    assert all(0 <= r["adapter"] < MIX["adapters"]["count"] for r in reqs)
    assert all(len(p) == MIX["prompt_len"] for p in prompts)
    assert all(0 <= p.min() and p.max() < 151936 for p in prompts)


def test_other_loops_and_popularities_are_refused():
    with pytest.raises(ValueError, match="loop"):
        traffic.ClosedLoop(dict(MIX, loop="open"), 0)
    mix = dict(MIX, adapters=dict(MIX["adapters"], popularity="zipf"))
    with pytest.raises(ValueError, match="popularity"):
        traffic.adapter_id(0, 0, mix)

"""The comparison that decides ``correct`` passes sound runs and fails the
control and every fault a served cell can have, each driven through a whole
run (`bench.run.run_cell`) at a tiny size on the CPU with the timed path
broken underneath."""
import jax
import jax.numpy as jnp
import pytest

from bench import run


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")


def serve(cell, seed, plant=None, control=None):
    return run.run_cell(cell, seed, 0.5, False, jax.devices(), plant=plant,
                        control=control)


def wrap_decode(ex, change):
    """Replace the executor's jitted decode step by ``change(out, args)``
    applied to its result."""
    inner = ex._decode

    def step(params, bundles, tokens, cache, ids, **kw):
        return change(inner(params, bundles, tokens, cache, ids, **kw),
                      (params, bundles, tokens, cache, ids))
    ex._decode = step


def state_unchanged(ex):
    # the step returns the cache it was given (a copy: the step donates
    # its own), so no token is ever written
    inner = ex._decode

    def step(params, bundles, tokens, cache, ids, **kw):
        kept = jax.tree.map(jnp.copy, cache)
        return inner(params, bundles, tokens, cache, ids, **kw)[0], kept
    ex._decode = step


def half_batch_left_out(ex):
    # the second half of the slots gets the mean of the first half's logits
    def change(out, args):
        lg = out[0]
        h = lg.shape[0] // 2
        return lg.at[h:].set(jnp.mean(lg[:h], 0, keepdims=True)), out[1]
    wrap_decode(ex, change)


def token_altered(ex):
    # every 50th decode step, slot 0 emits the token after its best
    n = {"steps": 0}

    def change(out, args):
        n["steps"] += 1
        lg = out[0]
        if n["steps"] % 50:
            return out
        bad = (jnp.argmax(lg[0, -1]) + 1) % ex.cfg.vocab_size
        return lg.at[0, -1, bad].set(1e4), out[1]
    wrap_decode(ex, change)


def adapters_misrouted(ex):
    # every slot decodes with its neighbour's adapter
    inner = ex._decode
    ex._decode = lambda p, b, t, c, ids, **kw: inner(p, b, t, c,
                                                     jnp.roll(ids, 1), **kw)


@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_sound_runs_pass_and_the_control_fails(tiny_cell, mode):
    """The float8 control, judged by the same comparison, is not correct on
    any seed; the program on the same requests is."""
    cell = tiny_cell(mode)
    for seed in (1, 2, 3):
        res = serve(cell, seed, control="fp8")
        assert res["correct"], res["checks"]
        assert res["failed"] == 0
        ctl = res["control"]
        assert ctl["correct"] is False, (seed, ctl["checks"])
        assert ctl["checks"]["widest_gap"]["value"] > \
            ctl["checks"]["widest_gap"]["limit"], seed
        assert ctl["checks"]["compared_tokens"]["value"] == \
            res["checks"]["compared_tokens"]["value"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered, adapters_misrouted])
def test_each_fault_is_not_correct(tiny_cell, fault):
    res = serve(tiny_cell("jd"), 4, plant=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered, adapters_misrouted])
def test_each_fault_is_not_correct_with_raw_lora(tiny_cell, fault):
    """The same faults under raw LoRA banks, the lora cell's adapters."""
    res = serve(tiny_cell("lora"), 4, plant=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]


def test_a_request_of_the_wrong_length_fails(tiny_cell, monkeypatch):
    """A finished request must carry exactly the mix's output length."""
    cell = tiny_cell("jd")
    real = run.Server._finish

    def finish(self, req):
        self.reqs[req.rid]["tokens"].pop()
        real(self, req)
    monkeypatch.setattr(run.Server, "_finish", finish)
    res = serve(cell, 5)
    assert not res["correct"]
    assert res["checks"]["bad_requests"]["value"] > 0

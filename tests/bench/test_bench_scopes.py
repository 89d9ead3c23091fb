"""Device time by operation name and by named scope: any kernel's events
land under its base name, and each operation of a program compiled here
on the CPU lands in the `jax.named_scope` path that its HLO instruction
carries, with the rest unscoped."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import spec, trace

WINDOW = [["bench.window", 0, 100_000]]


def test_a_kernel_no_file_knows_lands_in_op_s():
    ev = {"device": {"/device:TPU:0": [
        ["jit__step/brand_new_kernel", 1000, 2000, ""],
        ["jit__step/brand_new_kernel.3", 4000, 1000, ""],
        ["jit__step/fusion.12", 6000, 500, ""],
        ["jit__step/fused_decode_jd.2", 7000, 250, "fused_decode_jd"]]},
        "modules": {}, "host": WINDOW}
    r = trace.reduce(ev)
    assert r["op_s"] == {"brand_new_kernel": pytest.approx(3000e-9),
                         "fusion": pytest.approx(500e-9),
                         "fused_decode_jd": pytest.approx(250e-9)}
    # the named kernels' key keeps only the kernels it names
    assert r["kernel_s"] == {"fused_decode_jd": pytest.approx(250e-9)}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/qkv/dot_general", "qkv"),
    ("jit(f)/qkv/adapter_qkv/dot_general", "qkv/adapter_qkv"),
    ("jit(f)/while/body/closed_call/attention/sin", "attention"),
    ("jit(f)/attention/my_kernel/pallas_call", "attention/my_kernel"),
    ("jit(f)/mlp/jit(relu)/max", "mlp"),
    ("jit(f)/mlp/bsd,df->bsf/dot_general", "mlp"),
    ("jit(f)/cond/branch_1_fun/logits/add", "logits"),
    ("jit(f)/while", trace.UNSCOPED),
    ("x", trace.UNSCOPED),
])
def test_scope_of_an_op_name(op_name, scope):
    assert trace.scope_of(op_name) == scope


def _program(x, w):
    with jax.named_scope("qkv"):
        y = x @ w
        with jax.named_scope("adapter_qkv"):
            y = y + jnp.tanh(x) @ w

    def body(c, _):
        with jax.named_scope("attention"):
            c = jnp.sin(c) @ w
        return c, None
    y, _ = jax.lax.scan(body, y, None, length=2)
    with jax.named_scope("mlp"):
        y = jax.nn.relu(y @ w)
    with jax.named_scope("logits"):
        return (y @ w.T).sum()


@pytest.fixture(scope="module")
def compiled():
    """A small program compiled on the CPU, called once through a watched
    attribute as the executor's jitted steps are, and the scopes that
    `ProgramScopes` read from its compiled text."""
    owner = SimpleNamespace(step=jax.jit(_program))
    programs = trace.ProgramScopes(owner)
    programs.watch()
    x = jnp.ones((16, 16))
    owner.step(x, x).block_until_ready()
    owner.step(x, x + 1).block_until_ready()      # one program: one record
    programs.unwatch()
    assert owner.step.__name__ == "_program" and len(programs.calls) == 1
    scopes, clashes = programs.scopes()
    return scopes, clashes


def test_the_compiled_program_carries_its_named_scopes(compiled):
    scopes, clashes = compiled
    assert clashes == 0
    assert set(scopes) == {"jit__program"}
    found = set(scopes["jit__program"].values())
    assert {"qkv", "qkv/adapter_qkv", "attention", "mlp",
            "logits"} <= found
    assert found <= {"qkv", "qkv/adapter_qkv", "attention", "mlp", "logits",
                     trace.UNSCOPED}


def test_device_time_by_scope(compiled):
    """A hand-made trace of the program's instructions, 1 us each, one
    unknown to the program, and a loop whose body runs inside its event:
    every scope gets its instructions' time, the loop only its own."""
    scopes, _ = compiled
    by_instr = scopes["jit__program"]
    names = sorted(by_instr)
    ops, t = [], 1000
    for n in names:
        ops.append([f"jit__program/{n}", t, 1000, ""])
        t += 1000
    ops.append(["jit__program/copy.999", t, 1000, ""])      # not in the text
    t += 1000
    # a loop of 5 us whose two body operations take 1 us each
    loop = next(n for n in names if n.startswith("while"))
    ops.append([f"jit__program/{loop}", t, 5000, ""])
    att = [n for n in names if by_instr[n] == "attention"][:2]
    ops += [[f"jit__program/{att[0]}", t + 1000, 1000, ""],
            [f"jit__program/{att[1]}", t + 3000, 1000, ""]]
    ev = {"device": {"/device:TPU:0": ops}, "modules": {},
          "host": WINDOW, "scopes": scopes}
    r = trace.reduce(ev)["scope_s"]["jit__program"]
    want = {}
    for n in names:
        want[by_instr[n]] = want.get(by_instr[n], 0) + 1000e-9
    want[trace.UNSCOPED] += 1000e-9 + 3000e-9      # copy.999, the loop's own
    want["attention"] += 2000e-9
    assert r == {k: pytest.approx(v) for k, v in want.items()}
    total = (len(names) + 1 + 5) * 1000e-9
    assert sum(r.values()) == pytest.approx(total)
    # qkv holds its adapter's scope too
    assert trace.in_scope(r, "qkv") == pytest.approx(
        want["qkv"] + want["qkv/adapter_qkv"])
    # a program the scopes do not name is all unscoped
    ev["scopes"] = {}
    assert trace.reduce(ev)["scope_s"]["jit__program"] == {
        trace.UNSCOPED: pytest.approx(total)}


def test_programs_of_one_name_that_disagree_read_ambiguous():
    def f(x, *, flag):
        with jax.named_scope("a" if flag else "b"):
            return x * 3.0
    owner = SimpleNamespace(step=jax.jit(f, static_argnames="flag"))
    programs = trace.ProgramScopes(owner)
    programs.watch()
    for flag in (True, False):
        owner.step(jnp.ones(4), flag=flag)
    programs.unwatch()
    scopes, clashes = programs.scopes()
    assert clashes >= 1
    assert trace.AMBIGUOUS in scopes["jit_f"].values()


def test_scope_readers_per_decode_step():
    by = {"qkv": 0.010, "attention": 0.020, "attention/fused_decode_jd": 0.030,
          "mlp": 0.040, trace.UNSCOPED: 0.001}
    rec = trace.Record(reduced={"scope_s": {"jit__fused_decode_fn": by}},
                       spans=[("decode", 0.0, 1.0, {}),
                              ("decode", 1.0, 2.0, {})],
                       arch=None, adapters={}, peak={})
    assert spec.metric_reader("step.decode_attention_ms").read(rec) == \
        pytest.approx(25.0)
    assert spec.metric_reader("step.decode_mlp_ms").read(rec) == \
        pytest.approx(20.0)
    rec.reduced["scope_s"] = {}
    for name in ("step.decode_attention_ms", "step.decode_mlp_ms"):
        assert spec.metric_reader(name).read(rec) is None

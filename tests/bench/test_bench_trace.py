"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, on a hand-made trace whose answers are known, and the per-layer
readers on top of it."""
import jax
import pytest

from bench import costs, spec, trace

E = {
    "device": {"/device:TPU:0": [
        ["fusion.1", 1000, 2000, ""],
        ["custom-call.7", 2500, 1500, "fused_decode_jd"],   # overlaps fusion.1
        ["fusion.2", 6000, 1000, ""],
        ["custom-call.7", 9000, 3000, "fused_decode_jd"],   # runs past the end
        ["fusion.3", 500, 1000, ""],                         # starts before
    ]},
    "modules": {"/device:TPU:0": [
        ["jit__fused_decode_fn", 500, 3500],                 # starts before
        ["jit__prefill_fn", 6000, 1000],
        ["jit__fused_decode_fn", 9000, 3000],                # runs past the end
    ]},
    "host": [
        ["bench.window", 1000, 10000],
        ["bench.engine", 1000, 9500],
        ["bench.decode", 1000, 3500],
        ["bench.prefill", 5500, 3000],
    ],
}


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(E)
    # [1000, 4000] + [6000, 7000] + [9000, 11000]
    assert r["busy_s"] == pytest.approx(6000e-9)
    assert r["window_s"] == pytest.approx(10000e-9)


def test_kernel_time_is_clipped_to_the_window():
    assert trace.reduce(E)["kernel_s"] == {
        "fused_decode_jd": pytest.approx(3500e-9)}


def test_program_time_is_clipped_to_the_window():
    assert trace.reduce(E)["program_s"] == {
        "jit__fused_decode_fn": pytest.approx(5000e-9),
        "jit__prefill_fn": pytest.approx(1000e-9)}


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = trace.reduce(E)["breakdown"]["idle_gaps"]
    # (4000, 6000): middle 5000 is in engine only; (7000, 9000): middle
    # 8000 is in engine and prefill, prefill the shorter
    assert sorted(gaps) == [["engine", pytest.approx(2000e-9)],
                            ["prefill", pytest.approx(2000e-9)]]


def test_device_ops_longest_first():
    ops = trace.reduce(E)["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["custom-call.7", "fusion.1", "fusion.2",
                                   "fusion.3"]
    assert ops[0][1] == pytest.approx(3500e-9)


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 6), (0, 2), (2, 3), (1, 2), (7, 9), (8, 8)]) == \
        [(0, 3), (5, 6), (7, 9)]


@pytest.mark.parametrize("text,op,label", [
    ("%fused_decode_jd = (bf16[32,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)}, "
     "f32[32,8,2,1]) custom-call(%copy-done.474)", "fused_decode_jd",
     "fused_decode_jd"),
    ("%fused_decode_lora.27 = (bf16[32,8,2,128]) custom-call(%x)",
     "fused_decode_lora.27", "fused_decode_lora"),
    ("%fusion.12 = bf16[32,2048]{1,0} fusion(%p), kind=kLoop", "fusion.12",
     ""),
    ("%fused_decode_jd_paged = (bf16[1]) custom-call(%x)",
     "fused_decode_jd_paged", ""),
])
def test_kernels_are_found_by_instruction_name(text, op, label):
    assert trace.op_name(text) == op
    assert trace.kernel_of(op) == label


def test_program_names_drop_the_fingerprint():
    assert trace.module_name("jit__fused_decode_fn(9529663095814654421)") \
        == "jit__fused_decode_fn"


def test_a_trace_without_a_window_or_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce({"device": E["device"], "host": E["host"][1:]})
    with pytest.raises(ValueError, match="TPU"):
        trace.reduce({"device": {}, "host": E["host"]})


def record(spans, adapters=None):
    conf = spec.cell("qwen3-1.7b.jd1000.decode").config
    return trace.Record(reduced=trace.reduce(E), spans=spans,
                        arch=spec.arch("dense_gqa").arch(conf),
                        adapters=adapters or {"mode": "jd", "rank": 16,
                                              "targets": ["q", "k", "v", "o"]},
                        peak={"flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9})


def read(name, rec):
    return spec.metric_reader(name).read(rec)


def test_readers_on_the_hand_made_trace():
    spans = [("engine", 0.0, 0.010, {}),
             ("prefill", 0.001, 0.004, {"prompt_len": 512, "adapter": 1}),
             ("decode", 0.005, 0.008, {"kv_lens": [513, 513], "ids": [1, 2]}),
             ("engine", 0.010, 0.014, {}),
             ("decode", 0.010, 0.013, {"kv_lens": [514, 514], "ids": [1, 2]})]
    rec = record(spans)
    assert read("device.idle_share", rec) == pytest.approx(40.0)
    # (10 - 3 - 3) + (4 - 3) ms of host time over two steps
    assert read("engine.host_ms_per_step", rec) == pytest.approx(2.5)
    assert read("executor.prefill_ms", rec) == pytest.approx(3.0)
    assert read("executor.decode_step_ms", rec) == pytest.approx(3.0)
    a = rec.arch
    ad = rec.adapters
    least = sum(a.L * costs.least_seconds(*a.fused_decode_call(
        ad, kv, [1, 2]), rec.peak)[0] for kv in ([513] * 2, [514] * 2))
    assert read("fused_decode_jd_roofline", rec) == \
        pytest.approx(100 * least / 3500e-9)
    flops = a.prefill_flops(ad, 512) + sum(
        a.decode_step_flops(ad, kv) for kv in ([513] * 2, [514] * 2))
    assert read("step.mfu", rec) == pytest.approx(
        100 * flops / (10000e-9 * 197e12))
    nbytes = sum(a.decode_step_bytes(ad, kv, [1, 2])
                 for kv in ([513] * 2, [514] * 2))
    # over the fused decode program's device time, not the host spans
    assert read("step.decode_hbm_share", rec) == pytest.approx(
        100 * nbytes / (5000e-9 * 819e9))


def test_readers_with_nothing_to_read_return_none():
    rec = record([])
    for name in ("engine.host_ms_per_step", "executor.prefill_ms",
                 "executor.decode_step_ms", "fused_decode_jd_roofline",
                 "step.decode_hbm_share"):
        assert read(name, rec) is None, name
    # decode steps, but no run of the fused decode program in the trace
    rec = record([("decode", 0.005, 0.008, {"kv_lens": [513], "ids": [1]})])
    del rec.reduced["program_s"]["jit__fused_decode_fn"]
    assert read("step.decode_hbm_share", rec) is None


def test_normalize_keeps_the_benchmark_spans(tmp_path):
    """A CPU trace has no TPU plane; the host spans still come through."""
    f = jax.jit(lambda x: x * 2)
    f(1.0).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decode"):
            f(2.0).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.normalize(trace.newest_xplane(tmp_path))
    names = [n for n, _, _ in ev["host"]]
    assert names.count("bench.window") == 1 and "bench.decode" in names
    assert ev["device"] == {}



def test_a_recorded_v5e_trace():
    """Two decode steps of `qwen3-1.7b.jd1000.decode` traced on a TPU v5
    lite and normalized: the reduction's busy time agrees with a plain
    1-microsecond timeline of the same operations, the fused kernel runs
    once per layer in each decode step, and the fused decode program's
    time holds its kernels' and lies within the busy time."""
    import json
    from pathlib import Path

    import numpy as np

    ev = json.loads((Path(__file__).parent / "data" /
                     "trace_v5e_decode.json").read_text())
    ops = ev["device"]["/device:TPU:0"]
    r = trace.reduce(ev)
    w0, w1 = trace.window_of(ev)
    line = np.zeros((w1 - w0) // 1000 + 1, bool)
    for _, s, d, _ in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            line[(a - w0) // 1000:(b - w0 + 999) // 1000] = True
    assert r["busy_s"] == pytest.approx(line.sum() * 1e-6, rel=0.01)
    assert 0 < r["busy_s"] < r["window_s"]
    steps = [h for h in ev["host"] if h[0] == "bench.decode"
             and w0 <= h[1] and h[1] + h[2] <= w1]
    assert len(steps) == 2
    for _, s, d in steps:
        calls = [o for o in ops if o[3] == "fused_decode_jd" and
                 s <= o[1] < s + d]
        assert len(calls) == 28
        assert all(o[0].startswith("jit__fused_decode_fn/") for o in calls)
    assert r["kernel_s"]["fused_decode_jd"] > 0
    step = r["program_s"]["jit__fused_decode_fn"]
    assert r["kernel_s"]["fused_decode_jd"] < step <= r["busy_s"]
    assert {g[0] for g in r["breakdown"]["idle_gaps"]} <= {
        "engine", "decode", "prefill", "client", "outside_spans"}


# Each reader's value on the recorded v5e trace (its decode steps given
# kv_lens 600-631 and adapters i % 7) and on the hand-made trace, with the
# cost objects of both configurations, as the readers read them before the
# costs moved into bench/archs/dense_gqa.py.
PINNED = {
  "v5e": {
    "qwen3-1.7b.jd1000.decode": {
        "device.idle_share": 12.22556559938065,
        "engine.host_ms_per_step": 0.26081449999998396,
        "executor.prefill_ms": None,
        "executor.decode_step_ms": 29.391273249999962,
        "fused_decode_jd_roofline": 46.211949041661725,
        "step.mfu": 3.7386164659745442,
        "step.decode_hbm_share": 51.03936840001938,
    },
    "mistral-7b-16l.jd1000.decode": {
        "device.idle_share": 12.22556559938065,
        "engine.host_ms_per_step": 0.26081449999998396,
        "executor.prefill_ms": None,
        "executor.decode_step_ms": 29.391273249999962,
        "fused_decode_jd_roofline": 26.61960533563492,
        "step.mfu": 7.712791472022136,
        "step.decode_hbm_share": 76.34963100750282,
    },
  },
  "hand_made": {
    "qwen3-1.7b.jd1000.decode": {
        "device.idle_share": 40.0,
        "engine.host_ms_per_step": 2.5000000000000004,
        "executor.prefill_ms": 3.0,
        "executor.decode_step_ms": 2.9999999999999996,
        "fused_decode_jd_roofline": 8540.08498168498,
        "step.mfu": 75875.49329543146,
        "step.decode_hbm_share": 174439.38774114772,
    },
    "mistral-7b-16l.jd1000.decode": {
        "device.idle_share": 40.0,
        "engine.host_ms_per_step": 2.5000000000000004,
        "executor.prefill_ms": 3.0,
        "executor.decode_step_ms": 2.9999999999999996,
        "fused_decode_jd_roofline": 5062.9503575789295,
        "step.mfu": 185007.9616649746,
        "step.decode_hbm_share": 357629.7119413919,
    },
  },
}
JD = {"mode": "jd", "rank": 16, "targets": ["q", "k", "v", "o"], "clusters": 1}


def _recorded_v5e():
    import json
    from pathlib import Path

    ev = json.loads((Path(__file__).parent / "data" /
                     "trace_v5e_decode.json").read_text())
    kinds = {"bench.decode": "decode", "bench.prefill": "prefill",
             "bench.engine": "engine"}
    info = {"decode": {"kv_lens": [600 + i for i in range(32)],
                       "ids": [i % 7 for i in range(32)]},
            "prefill": {"prompt_len": 512, "adapter": 3}, "engine": {}}
    spans = [(kinds[n], s / 1e9, (s + d) / 1e9, info[kinds[n]])
             for n, s, d in ev["host"] if n in kinds]
    return ev, spans


HAND_MADE_SPANS = [
    ("engine", 0.0, 0.010, {}),
    ("prefill", 0.001, 0.004, {"prompt_len": 512, "adapter": 1}),
    ("decode", 0.005, 0.008, {"kv_lens": [513, 513], "ids": [1, 2]}),
    ("engine", 0.010, 0.014, {}),
    ("decode", 0.010, 0.013, {"kv_lens": [514, 514], "ids": [1, 2]})]


@pytest.mark.parametrize("source", ["v5e", "hand_made"])
@pytest.mark.parametrize("cell", ["qwen3-1.7b.jd1000.decode",
                                  "mistral-7b-16l.jd1000.decode"])
def test_readers_read_what_they_read_before(source, cell):
    ev, spans = _recorded_v5e() if source == "v5e" else (E, HAND_MADE_SPANS)
    conf = spec.cell(cell).config
    rec = trace.Record(reduced=trace.reduce(ev), spans=spans,
                       arch=spec.arch(conf["reference"]).arch(conf),
                       adapters=JD, peak={"flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    for name, want in PINNED[source][cell].items():
        got = read(name, rec)
        if want is None:
            assert got is None, name
        else:
            assert got == pytest.approx(want, rel=1e-12), name

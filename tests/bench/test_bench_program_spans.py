"""The per-layer metrics that read the program's own spans
(`repro.serving.telemetry`): on hand-made records with known answers, with
nothing to read, and on the spans of a tiny cell served on the CPU through
`ServingEngine` over `RealModelExecutor`."""
import sys
from types import SimpleNamespace

import jax
import pytest

from bench import run, spec
from repro.serving import telemetry

NAMES = ("engine.prefill_wait_ms", "executor.decode_launch_ms",
         "executor.decode_fetch_ms")
DECODE_PARTS = ["executor.decode." + p for p in
                ("inputs", "launch", "sample", "wait", "fetch", "emit")]


def read(name, rec):
    return spec.metric_reader(name).read(rec)


def R(name, start, end, rid=None, parent=None, id=0):
    return telemetry.Record(name, start, end, parent, rid, {}, id)


# the benchmark's spans cover [1.0, 2.0]
WINDOW = SimpleNamespace(spans=[("engine", 1.0, 2.0, {}),
                                ("decode", 1.2, 1.5, {})])

HAND_MADE = [
    R("engine.submit", 0.5, 0.5, rid=1),            # before the window
    R("engine.submit", 1.05, 1.05, rid=2),
    R("executor.prefill", 1.1, 1.13, rid=1),        # waited 600 ms
    R("executor.prefill", 1.15, 1.18, rid=2),       # waited 100 ms
    R("executor.prefill", 1.19, 1.2, rid=4),        # never submitted
    R("engine.submit", 2.4, 2.4, rid=3),
    R("executor.prefill", 2.5, 2.53, rid=3),        # after the window
    # two decode steps in the window, one after it
    R("executor.decode.inputs", 1.2, 1.2002),
    R("executor.decode.launch", 1.2002, 1.2005),
    R("executor.decode.fetch", 1.45, 1.452),
    R("executor.decode", 1.2, 1.5),
    R("executor.decode.inputs", 1.6, 1.6001),
    R("executor.decode.launch", 1.6001, 1.6005),
    R("executor.decode.fetch", 1.85, 1.851),
    R("executor.decode", 1.6, 1.9),
    R("executor.decode.inputs", 2.1, 2.2),
    R("executor.decode.launch", 2.2, 2.3),
    R("executor.decode.fetch", 2.3, 2.4),
    R("executor.decode", 2.1, 2.4),
]


def test_readers_on_hand_made_records(monkeypatch):
    monkeypatch.setattr(telemetry, "records", lambda: list(HAND_MADE))
    assert read("engine.prefill_wait_ms", WINDOW) == pytest.approx(350.0)
    # (0.2 + 0.3) and (0.1 + 0.4) ms over two steps
    assert read("executor.decode_launch_ms", WINDOW) == pytest.approx(0.5)
    assert read("executor.decode_fetch_ms", WINDOW) == pytest.approx(1.5)


def test_the_wait_is_from_the_latest_submit_before_the_prefill(monkeypatch):
    recs = [R("engine.submit", 0.2, 0.2, rid=1),
            R("engine.submit", 0.9, 0.9, rid=1),     # submitted again
            R("executor.prefill", 1.1, 1.2, rid=1),
            R("engine.submit", 1.5, 1.5, rid=1)]     # after its prefill
    monkeypatch.setattr(telemetry, "records", lambda: recs)
    assert read("engine.prefill_wait_ms", WINDOW) == pytest.approx(200.0)


@pytest.mark.parametrize("name", NAMES)
def test_readers_with_nothing_to_read_return_none(name, monkeypatch):
    monkeypatch.setattr(telemetry, "records", lambda: list(HAND_MADE))
    assert read(name, SimpleNamespace(spans=[])) is None
    # records, but none of the reader's inside the window
    assert read(name, SimpleNamespace(spans=[("engine", 3.0, 4.0, {})])) \
        is None
    monkeypatch.setattr(telemetry, "records", lambda: [])
    assert read(name, WINDOW) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_recorder_reads_none(name, monkeypatch):
    # a program without the recorder: importing it fails
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert read(name, WINDOW) is None


@pytest.fixture
def served(tiny_cell, monkeypatch):
    """The program's records of one short run of the tiny jd cell, and the
    benchmark's server, kept from the run's end-to-end reading."""
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    kept = {}
    inner = run.end_to_end

    def end_to_end(server, t0, t1):
        kept.update(server=server, t0=t0, t1=t1)
        return inner(server, t0, t1)
    monkeypatch.setattr(run, "end_to_end", end_to_end)
    telemetry.clear()
    res = run.run_cell(tiny_cell("jd"), 5, 0.5, False, jax.devices())
    assert res["correct"]
    yield telemetry.records(), kept
    telemetry.clear()


def test_the_served_path_records_its_layers(served):
    records, kept = served
    by_id = {r.id: r for r in records}
    kids = {}
    for r in records:
        kids.setdefault(r.parent, []).append(r)
    steps = [r for r in records if r.name == "executor.decode"]
    assert steps
    for s in steps:
        assert [c.name for c in sorted(kids[s.id], key=lambda c: c.start)] \
            == DECODE_PARTS
        outer = by_id[s.parent]
        assert outer.name == "engine.decode"
        assert [c.name for c in kids[outer.id]] == ["executor.decode"]
        assert by_id[outer.parent].name == "engine.step"
        assert s.attrs["slots"] == 8 and 1 <= s.attrs["batch"] <= 8
        assert s.attrs["bucket"] == 128          # s_max: one KV bucket
    prefills = [r for r in records if r.name == "executor.prefill"]
    assert prefills
    submitted = {r.rid: r.start for r in records if r.name == "engine.submit"}
    for p in prefills:
        assert submitted[p.rid] <= p.start
        assert by_id[p.parent].name == "engine.admit"
        assert [c.name for c in sorted(kids[p.id], key=lambda c: c.start)] \
            == ["executor.prefill." + k for k in
                ("cache", "run", "splice", "sample", "wait", "fetch")]
    assert not any(r.name.startswith("bench.") for r in records)


def test_the_readers_read_the_served_path(served):
    records, kept = served
    server, t0, t1 = kept["server"], kept["t0"], kept["t1"]
    rec = SimpleNamespace(spans=[s for s in server.spans
                                 if s[1] >= t0 and s[2] <= t1])
    for name in NAMES:
        v = read(name, rec)
        assert v is not None and v > 0, name
    # each program span lies inside the benchmark's span of the same call
    bench_decode = [(s, e) for k, s, e, _ in server.spans if k == "decode"]
    for r in records:
        if r.name == "executor.decode":
            assert any(s <= r.start and r.end <= e for s, e in bench_decode)

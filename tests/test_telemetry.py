"""The served path's recorder (`repro.serving.telemetry`): spans nest and
keep their request id and attributes, the record deque stays bounded, a
span whose body raises still closes, and the spans reach the profiler's
trace with their stats."""
from pathlib import Path

import jax
import pytest

from repro.serving import telemetry


@pytest.fixture(autouse=True)
def empty():
    telemetry.clear()
    yield
    telemetry.clear()


def by_name():
    return {r.name: r for r in telemetry.records()}


def test_spans_nest_and_keep_rid_and_attrs():
    with telemetry.span("engine.step", running=3) as outer:
        telemetry.event("engine.submit", rid=7)
        with telemetry.span("executor.prefill", rid=7, prompt_len=12,
                            slot=0) as inner:
            with telemetry.span("executor.prefill.run"):
                pass
    r = by_name()
    assert [x.name for x in telemetry.records()] == [
        "engine.submit", "executor.prefill.run", "executor.prefill",
        "engine.step"]
    assert r["engine.step"].parent is None
    assert r["engine.step"].id == outer.id
    assert r["engine.step"].attrs == {"running": 3}
    assert r["engine.submit"].parent == outer.id
    assert r["engine.submit"].rid == 7
    assert r["engine.submit"].start == r["engine.submit"].end
    assert r["executor.prefill"].parent == outer.id
    assert r["executor.prefill"].id == inner.id
    assert r["executor.prefill"].rid == 7
    assert r["executor.prefill"].attrs == {"prompt_len": 12, "slot": 0}
    assert r["executor.prefill.run"].parent == inner.id
    assert r["executor.prefill.run"].rid is None
    # children lie inside their parents, on one clock
    for child, parent in (("executor.prefill.run", "executor.prefill"),
                          ("executor.prefill", "engine.step"),
                          ("engine.submit", "engine.step")):
        assert r[parent].start <= r[child].start <= r[child].end \
            <= r[parent].end


def test_siblings_share_a_parent_and_ids_are_distinct():
    with telemetry.span("executor.decode") as step:
        for part in ("inputs", "launch", "sample"):
            with telemetry.span("executor.decode." + part):
                pass
    kids = [r for r in telemetry.records() if r.parent == step.id]
    assert [k.name for k in kids] == ["executor.decode.inputs",
                                      "executor.decode.launch",
                                      "executor.decode.sample"]
    assert len({r.id for r in telemetry.records()}) == 4
    assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))


def test_the_deque_stays_bounded_and_keeps_the_newest():
    n = telemetry.MAX_RECORDS + 100
    for i in range(n):
        telemetry.event("engine.submit", rid=i)
    kept = telemetry.records()
    assert len(kept) == telemetry.MAX_RECORDS
    assert kept[0].rid == 100 and kept[-1].rid == n - 1


def test_a_span_closes_when_its_body_raises():
    with pytest.raises(RuntimeError, match="boom"):
        with telemetry.span("engine.step"):
            with telemetry.span("engine.decode"):
                raise RuntimeError("boom")
    assert [r.name for r in telemetry.records()] == ["engine.decode",
                                                    "engine.step"]
    # the enclosing span is reset: a later span is at the top level
    with telemetry.span("engine.step"):
        pass
    assert telemetry.records()[-1].parent is None


def test_clear_empties_the_records():
    telemetry.event("engine.submit", rid=1)
    telemetry.clear()
    assert telemetry.records() == []


def _host_events(xplane: Path):
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, dict(e.stats)))
    return out


def test_spans_reach_the_profilers_trace_with_their_rid(tmp_path):
    """On a CPU profile, as the benchmark reads a chip's: each span and
    event is a host event of its name, its rid and attributes are stats."""
    f = jax.jit(lambda x: x * 2)
    f(1.0).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        telemetry.event("engine.submit", rid=5)
        with telemetry.span("executor.prefill", rid=5, prompt_len=3,
                            slot=1):
            with telemetry.span("executor.prefill.run"):
                f(2.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    events = _host_events(found[-1])
    names = [n for n, _ in events]
    for name in ("engine.submit", "executor.prefill", "executor.prefill.run"):
        assert names.count(name) == 1, name
    stats = {n: s for n, s in events}
    assert int(stats["executor.prefill"]["rid"]) == 5
    assert int(stats["executor.prefill"]["prompt_len"]) == 3
    assert int(stats["engine.submit"]["rid"]) == 5
    assert not any(n.startswith("bench.") for n in names)

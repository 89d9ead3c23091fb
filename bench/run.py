"""Runs one benchmark cell once on the chip it is started on.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a model configuration and a traffic mix.
The run makes the weights and the adapter collection on the device from
``--seed``, builds the served path (`RealModelExecutor` on the fused decode
path under a `ServingEngine`), serves one whole wave of the closed loop to
compile and warm every shape, and then drives `ServingEngine.step()` from
the loop's clients for ``--seconds``.  Times come from the host clock only.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
one wave with the JAX profiler and reports the per-layer metrics, each read
by ``bench/metrics/<name>.py``.  After the window, a sample of the finished
requests is compared with the plain float32 reference (`bench.correct`).

Earlier lines go to standard error; the last line of standard output is the
result as one JSON object.  A platform other than ``tpu`` exits 1 first.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, Optional

from bench import correct, spec, stats, traffic
from bench import trace as trace_mod
from bench.compile_clock import CompileClock

ROOT = spec.ROOT
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"bench.run: FAIL: {msg}")
    sys.exit(1)


def process_age() -> float:
    """Seconds since this process started, from the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def use_compile_cache() -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at :data:`CACHE_DIR` in the checkout; every program is
    kept, however fast it compiled."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


class Server:
    """The closed loop around one engine: clients send, the engine serves,
    and the benchmark's own wrappers time the executor's calls.

    Spans are ``(kind, start, end, info)`` in `time.perf_counter` seconds,
    each also a `jax.profiler.TraceAnnotation` named ``bench.<kind>``."""

    def __init__(self, eng, ex, loop: traffic.ClosedLoop, vocab: int):
        import jax

        self.annotate = jax.profiler.TraceAnnotation
        self.eng, self.ex, self.loop, self.vocab = eng, ex, loop, vocab
        self.reqs: Dict[int, Dict] = {}
        self.spans = []
        self.sending = True
        ex.prefill_time = self._prefill
        ex.decode_step_time = self._decode
        eng.on_finish = self._finish

    def send(self, r: Dict) -> None:
        from repro.serving.request import Request

        self.reqs[r["rid"]] = {"adapter": r["adapter"], "sent": time.perf_counter(),
                               "prompt_len": r["prompt_len"], "first": None,
                               "tokens": [], "times": [], "finished": None}
        self.eng.submit([Request(rid=r["rid"], adapter_id=r["adapter"],
                                 prompt_len=r["prompt_len"],
                                 max_new_tokens=r["output_len"],
                                 arrival_time=self.eng.clock)])

    def _prefill(self, req) -> float:
        prompt = traffic.prompt_tokens(self.loop.seed, req.rid, req.prompt_len,
                                       self.vocab)
        with self.annotate("bench.prefill"):
            t0 = time.perf_counter()
            self.ex.prefill_request(req, prompt)
            t1 = time.perf_counter()
        slot = self.ex.slot_req.index(req.rid)
        self.reqs[req.rid]["first"] = int(self.ex.slot_tokens[slot])
        self.spans.append(("prefill", t0, t1, {"prompt_len": req.prompt_len,
                                               "adapter": req.adapter_id}))
        return t1 - t0

    def _decode(self, batch) -> float:
        kv = [r.prompt_len + r.generated + 1 for r in batch]
        ids = [r.adapter_id for r in batch]
        with self.annotate("bench.decode"):
            t0 = time.perf_counter()
            out = self.ex.decode_step_real()
            t1 = time.perf_counter()
        for rid, tok in out.items():
            self.reqs[rid]["tokens"].append(tok)
            self.reqs[rid]["times"].append(t1)
        self.spans.append(("decode", t0, t1, {"kv_lens": kv, "ids": ids}))
        return t1 - t0

    def _finish(self, req) -> None:
        self.ex.release(req.rid)
        self.reqs[req.rid]["finished"] = time.perf_counter()
        if self.sending:
            with self.annotate("bench.client"):
                self.send(self.loop.send())

    def step(self) -> None:
        with self.annotate("bench.engine"):
            t0 = time.perf_counter()
            self.eng.step()
            t1 = time.perf_counter()
        self.spans.append(("engine", t0, t1, {}))

    def serve_until(self, done: Callable[[], bool]) -> None:
        while not done():
            self.step()


def end_to_end(server: Server, t0: float, t1: float):
    """The end-to-end metrics of the window [t0, t1] on the host clock, and
    how many samples each rests on."""
    n_tok, ttft, tbt = 0, [], []
    for r in server.reqs.values():
        times = r["times"]
        n_tok += sum(1 for t in times if t0 <= t <= t1)
        if times and t0 <= times[0] <= t1:
            ttft.append(times[0] - r["sent"])
        tbt += [b - a for a, b in zip(times, times[1:]) if t0 <= a and b <= t1]
    return ({"out_tok_s": stats.rate(n_tok, t1 - t0),
             "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
             "tbt_p95_ms": 1e3 * stats.percentile(tbt, 95)},
            {"tokens": n_tok, "ttft": len(ttft), "tbt": len(tbt)})


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, plant: Optional[Callable] = None,
             control: Optional[str] = None) -> Dict:
    """One run of ``cell``; returns the result object.  ``plant(ex)`` may
    replace parts of the executor before serving (tests of the check);
    with ``control``, the control in that precision is judged by the same
    comparison too (`bench.correct.control`), under ``"control"``."""
    import jax

    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.real_executor import RealModelExecutor
    from repro.serving.scheduler import SchedulerConfig

    from bench import weights

    clock = CompileClock()
    cache = use_compile_cache()
    conf, tr = cell.config, cell.traffic
    ad = tr["adapters"]
    arch = spec.arch(conf["reference"])
    cfg = arch.model_config(conf, tr)
    init = float(conf["initializer_range"])
    params = weights.make_params(cfg, seed, init)
    bundles = weights.make_adapters(cfg, arch.adapter_dims(conf), ad, seed,
                                    init)
    jax.block_until_ready((params, bundles))

    B = int(tr["max_batch"])
    ex = RealModelExecutor(cfg, params, bundles, ad["mode"], B,
                           int(tr["s_max"]), decode_path="fused", seed=seed)
    if plant is not None:
        plant(ex)
    eng = ServingEngine(EngineConfig(
        scheduler=SchedulerConfig(max_batch=B, max_adapters_per_batch=B),
        adapter_budget_bytes=1e12, mode=ad["mode"], decode_path="fused"), ex)
    loop = traffic.ClosedLoop(tr, seed)
    server = Server(eng, ex, loop, int(conf["vocab_size"]))

    # warm-up: one whole wave compiles prefill and every KV bucket
    for r in loop.first_wave():
        server.send(r)
    warm = set(server.reqs)
    server.serve_until(lambda: all(server.reqs[i]["finished"] for i in warm))
    before = clock.snapshot()
    setup_s = process_age()
    log(f"# set-up {setup_s:.3f} s; compile cache {cache}; {before}")

    wave = set(range(loop.next_rid - loop.clients, loop.next_rid))
    tracer = _Tracer(trace, ex)
    tracer.start()
    t0 = time.perf_counter()
    if trace:
        # one whole wave, or the window if it ends first
        server.serve_until(lambda: time.perf_counter() >= t0 + seconds or all(
            server.reqs[i]["finished"] for i in wave))
    else:
        server.serve_until(lambda: time.perf_counter() >= t0 + seconds)
    t_end = time.perf_counter()
    tracer.stop()
    t1 = min(t_end, t0 + seconds)
    during = clock.snapshot()
    in_window = {k: during[k] - before[k] for k in before}
    log(f"# in the window: {in_window}")

    # requests sent in the window; finish enough of them for the check
    server.sending = False
    sent = [i for i, r in server.reqs.items() if r["sent"] >= t0 or i in wave]
    need = int(tr.get("check_requests", 1))
    server.serve_until(lambda: sum(
        1 for i in sent if server.reqs[i]["finished"]) >= min(need, len(sent))
        or not (server.eng.running or server.eng.waiting))

    dev = devices[0]
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    log(f"# peak_bytes_in_use {peak_bytes}")
    result = {"correct": False, "attempted": len(sent), "failed": 0,
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak_bytes}}

    if trace:
        rec = tracer.record(server, t0, t_end, arch.arch(conf), ad,
                            dev.device_kind)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = rec.reduced["busy_s"]
        result["device"]["window_s"] = rec.reduced["window_s"]
        result["breakdown"] = rec.reduced["breakdown"]
        for line in rec.notes:
            log(line)
    else:
        e2e, samples = end_to_end(server, t0, t1)
        log(f"# samples in the window: {samples}")
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if k in units}

    served = [{"rid": i, "adapter": server.reqs[i]["adapter"],
               "prompt": traffic.prompt_tokens(seed, i, server.reqs[i]["prompt_len"],
                                               int(conf["vocab_size"])),
               "first": server.reqs[i]["first"],
               "tokens": list(server.reqs[i]["tokens"])}
              for i in sent if server.reqs[i]["finished"]]
    # the program's state goes before the reference runs
    del server, eng, ex, tracer
    gc.collect()
    verdict = correct.check(conf, tr, cell.limits, params, bundles, served,
                            seed)
    if control is not None:
        result["control"] = correct.check(
            conf, tr, cell.limits, params, bundles,
            correct.control(conf, tr, params, bundles, served, seed, control),
            seed)
    result["correct"] = verdict["correct"]
    result["failed"] = verdict["failed"]
    result["checks"] = verdict["checks"]
    return result


class _Tracer:
    """Profiler capture of the traced window, and its reduction; the
    executor's programs run in the window give the named scopes."""

    def __init__(self, on: bool, ex):
        self.on = on
        self.path = TRACE_DIR
        self.programs = trace_mod.ProgramScopes(ex)
        self._window = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        self.programs.watch()
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.programs.unwatch()

    def record(self, server, t0, t1, arch, ad, device_kind):
        from bench import peaks

        try:
            events = trace_mod.normalize(trace_mod.newest_xplane(self.path))
        finally:
            shutil.rmtree(self.path, ignore_errors=True)
        events["scopes"], clashes = self.programs.scopes()
        rec = trace_mod.Record(
            reduced=trace_mod.reduce(events),
            spans=[s for s in server.spans if s[1] >= t0 and s[2] <= t1],
            arch=arch, adapters=ad, peak=peaks.peaks(device_kind))
        for prog, by in rec.reduced["scope_s"].items():
            if prog in events["scopes"]:
                kernels = sorted({(trace_mod.kernel_of(i), where) for i, where
                                  in events["scopes"][prog].items()
                                  if trace_mod.kernel_of(i)})
                rec.notes.append(f"# scope_s {prog} (program "
                                 f"{rec.reduced['program_s'].get(prog)} s): "
                                 f"{json.dumps(by, sort_keys=True)}; "
                                 f"kernels in {kernels}")
        if clashes:
            rec.notes.append(f"# scopes: {clashes} instructions in "
                             f"different scopes in programs of one name")
        return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"needs a TPU; JAX found platform {platform!r}")
    cell = spec.cell(args.workload)
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips; JAX found "
             f"{len(devices)}")
    from bench import peaks

    peaks.peaks(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

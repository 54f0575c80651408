"""One run of one benchmark cell: set-up, an open loop on the real clock,
the check against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:

- ``BENCHMARK.json`` names each cell's configuration and traffic mix, and
  gives each configuration's file;
- a traffic mix is ``bench/traffic/<traffic>.json`` (``synth.TrafficParams``
  plus the warm-up and trace lengths);
- a per-layer metric is ``bench/metrics/<name>.py``, whose ``read(run)``
  returns a number, or None when the run holds nothing to read.

The window drives one serving replica's documented live mode:
``QACOnlineRuntime.submit`` at each request's due time, stamped with it, and
``tick`` between arrivals, over ``QACFrontend``. A request's latency runs
from its due time to the moment its answer is on the host here.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
SLA_MS = 50.0            # interactive limit (QACArch.obs_slo_target_us)
DRAIN_CAP_S = 120.0      # a window request unanswered by then has failed
N_CHECK = 40             # sampled answers per (query class, cache path)
TRACE_LEAD_S = 0.5       # the profiler starts (a 40 ms stall) this early


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the spec, found by name ------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: Path             # where BENCHMARK.json and the data files lie


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)],
                root=root)


def draft_cell(config: str, traffic: str, root: Path = ROOT) -> Cell:
    """A configuration under a traffic mix that ``BENCHMARK.json`` may not
    hold yet (``bench/configs/<config>.json``, ``bench/traffic/<traffic>
    .json``): what the sweep offers before a cell is added. One chip."""
    return Cell(
        name=f"{config}.{traffic}", chips=1,
        config=json.loads(
            (root / "bench" / "configs" / f"{config}.json").read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=[], per_layer=[], root=root)


def load_module(path: Path):
    """Import a benchmark file by its path (names may hold dots)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in path.with_suffix("").name)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The per-layer metric ``name``'s ``read`` function."""
    return load_module(BENCH / "metrics" / f"{name}.py").read


def traffic_params(traffic: dict):
    from .synth import TrafficParams

    fields = {f.name for f in dataclasses.fields(TrafficParams)}
    return TrafficParams(**{k: v for k, v in traffic.items() if k in fields})


# -- devices ------------------------------------------------------------------
def tpu_devices(chips: int):
    """The TPU devices, or ``NoChip``: never a CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def enable_compile_cache(root: Path = ROOT) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<root>/.jax_cache``:
    one fixed path, so every run after a cell's first finds its programs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the serving variants compile in well under the default 1 s floor;
    # without this they would compile again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- set-up -----------------------------------------------------------------
@dataclasses.dataclass
class System:
    """What set-up hands the window: the runtime over its frontend, the
    requests with their due times, and the reference for the check."""

    rt: object
    fe: object
    auditor: object
    qidx: object
    ref: object
    spans: list                # (t0, t1, rows, any multi-term) per complete()
    k: int
    max_batch: int
    dataset_seed: int
    reqs: list = dataclasses.field(default_factory=list)
    due_s: np.ndarray = None   # per request, seconds from the traffic epoch


def build_system(cell: Cell, *, wrap_frontend=None) -> System:
    """The configuration's corpus (from its dataset seed), the plain
    reference over it, the program's index build and device put, and the
    serving replica over it."""
    import jax

    from repro.core import build_qac_index
    from repro.obs.jit_audit import JitAuditor
    from repro.serve.frontend import QACFrontend
    from repro.serve.runtime import QACOnlineRuntime, RuntimeConfig

    from .synth import LogParams, generate_query_log

    conf = cell.config
    parse, serving = conf["parse"], conf["serving"]
    t0 = time.perf_counter()
    qlog = generate_query_log(LogParams(**conf["generator"]),
                              (conf["dataset_seed"], 0))
    t1 = time.perf_counter()
    reference = load_module(cell.root / conf["reference"])
    # the reference is numpy over the drawn ids and releases the GIL in
    # its sorts, so it builds beside the program's index build
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        pending = pool.submit(
            reference.ExactTopK, qlog.vocab, qlog.term_idx, qlog.n_terms,
            qlog.scores, max_terms=parse["max_terms"],
            max_term_chars=parse["max_term_chars"])
        qidx, kept, _ = build_qac_index(qlog.queries, qlog.scores,
                                        k_default=conf["k"])
        jax.block_until_ready(qidx)
        t2 = time.perf_counter()
        ref = pending.result()
    t3 = time.perf_counter()
    n_kept = len(kept)
    del qlog, kept
    log(f"corpus: {n_kept} completions, {qidx.dictionary.n_terms} terms; "
        f"log {t1 - t0:.1f}s, index build {t2 - t1:.1f}s, reference "
        f"{t3 - t2:.1f}s after it")
    if n_kept != len(ref):
        raise RuntimeError(f"the program kept {n_kept} completions, the "
                           f"reference {len(ref)}")
    auditor = JitAuditor()
    fe = QACFrontend(qidx, k=conf["k"],
                     specialize_list_pad=serving["specialize_list_pad"],
                     auditor=auditor)
    if wrap_frontend is not None:
        # a fault planted under the timed path, or the control put in the
        # program's place (it reads the reference)
        wrap_frontend(fe, ref)
    spans: list = []
    fe.complete = _spanned(fe.complete, spans)
    rt = QACOnlineRuntime(fe, RuntimeConfig(
        max_batch=serving["max_batch"], slack_us=serving["slack_us"],
        cache_entries=serving["cache_entries"],
        session_entries=serving["session_entries"]))
    return System(rt=rt, fe=fe, auditor=auditor, qidx=qidx, ref=ref,
                  spans=spans, k=conf["k"], max_batch=serving["max_batch"],
                  dataset_seed=conf["dataset_seed"])


def offer(sys_: System, traffic: dict, seconds: float, seed: int) -> None:
    """The traffic mix's requests for its warm-up and a window of
    ``seconds``, parsed by the program's ``prepare_requests``."""
    from repro.serve.runtime import prepare_requests

    from .synth import draw_sessions, schedule

    warm_s = float(traffic["warm_seconds"])
    span_s = warm_s + seconds
    sessions, starts = draw_sessions(
        len(sys_.ref), sys_.ref.completion, traffic_params(traffic), warm_s,
        seconds, (sys_.dataset_seed, 1))
    events = schedule(sessions, starts, span_s, (seed, 1))
    sys_.reqs = prepare_requests(sys_.qidx, events, k=sys_.k)
    sys_.due_s = np.asarray([r.t_us for r in sys_.reqs]) / 1e6
    n_multi = sum(r.plen > 0 for r in sys_.reqs)
    log(f"traffic: {len(sys_.reqs)} requests over {span_s:.0f}s "
        f"({len(sys_.reqs) / span_s:.1f} req/s; "
        f"{len(sys_.reqs) - n_multi} single-term, {n_multi} multi-term)")


def warm_variants(sys_: System) -> None:
    """The runtime's warm-up sweep over the offered requests: the jit
    variants of the query classes this traffic holds, and no others."""
    t0 = time.perf_counter()
    # the sweep only has to compile: it is given each class's cheapest
    # requests, single-term ones and multi-term ones with an empty suffix
    # (the first k postings of the prefix terms answer them)
    multi = [r for r in sys_.reqs if r.plen > 0]
    sys_.rt.warmup([r for r in sys_.reqs if r.plen == 0]
                   + ([r for r in multi if r.slen == 0] or multi))
    fe = sys_.fe
    # the sweep leaves the single-term engine's exact fallback cold, and a
    # lane that needs it would compile it inside the window: warm it for
    # every bucket the sweep formed. Only the frontend's own variant cache
    # (``_get``) reaches it; without it the run stops here, rather than
    # compile in the window
    single = [r for r in sys_.reqs if r.plen == 0]
    if single and not hasattr(fe, "_get"):
        raise RuntimeError("QACFrontend._get is gone: the single-term exact "
                           "fallback cannot be warmed before the window")
    if single:
        b = fe.min_bucket
        while b <= max(sys_.max_batch, fe.min_bucket):
            rows = [single[j % len(single)] for j in range(b)]
            np.asarray(fe._get("single_full", b, sys_.k)(
                np.stack([r.suf for r in rows]),
                np.asarray([r.slen for r in rows], np.int32)))
            b *= 2
    log(f"warm-up sweep: {len(sys_.auditor.compiles)} jit variants in "
        f"{time.perf_counter() - t0:.1f}s; routes: single-term "
        f"{fe.describe_route('single')}, multi-term "
        f"{fe.describe_route('multi', sys_.max_batch, fe.list_pad)}")
    sys_.spans.clear()


def settle() -> None:
    """Take set-up's objects (the reference, the parsed traffic) out of the
    garbage collector's scans, so that its pauses in the window are the
    server's own."""
    gc.collect()
    gc.freeze()


def _spanned(complete, spans: list):
    """The harness's own span around the public ``QACFrontend.complete``:
    host wall time per call, and a profiler annotation for the trace."""
    import jax

    def timed(prefix_ids, prefix_len, *args, **kwargs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("complete"):
            out = complete(prefix_ids, prefix_len, *args, **kwargs)
        t1 = time.perf_counter()
        plen = np.asarray(prefix_len)
        spans.append((t0, t1, int(plen.size), bool((plen > 0).any())))
        return out

    return timed


# -- the window ---------------------------------------------------------------
@dataclasses.dataclass
class Window:
    """Per request of the whole offered trace: submit time and answer time
    (seconds from the epoch, NaN when never), and the window's bounds."""

    start: float
    end: float
    submit: np.ndarray
    done: np.ndarray
    batches: tuple             # (first, last) index into batch_sizes
    trace_dir: str | None = None
    traced: tuple | None = None   # (t0, t1) of the traced stretch
    traced_batches: tuple | None = None
    epoch: float = 0.0


def serve(sys_: System, warm_s: float, seconds: float, *, trace_s: float = 0,
          trace_dir: str | None = None,
          drain_cap_s: float = DRAIN_CAP_S) -> Window:
    """Offer every request at its due time and wait for the window's
    answers. Requests due after the window are never offered."""
    import jax

    rt, reqs, due = sys_.rt, sys_.reqs, sys_.due_s
    n = int(np.searchsorted(due, warm_s + seconds))
    submit = np.full(len(reqs), np.nan)
    done = np.full(len(reqs), np.nan)
    pending: set = set()
    tel = rt.telemetry
    ann = (jax.profiler.TraceAnnotation if trace_dir
           else lambda name: contextlib.nullcontext())
    tracing = traced = traced_batches = None
    profiling = False
    t_on = warm_s + seconds - min(trace_s, seconds)   # the traced stretch
    clock = time.perf_counter
    epoch = clock()

    def collect():
        if pending:
            t = clock() - epoch
            for i in [i for i in pending if i in rt.done_path]:
                done[i] = t
                pending.discard(i)

    batch0 = None
    i = 0
    while i < n or pending:
        now = clock() - epoch
        if batch0 is None and now >= warm_s:
            sys_.auditor.freeze()
            batch0 = len(tel.batch_sizes)
        if trace_dir and not profiling and traced is None and \
                now >= t_on - TRACE_LEAD_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            profiling = True
        if profiling and traced is None and now >= t_on:
            tracing = ann("window")
            tracing.__enter__()
            traced = (clock() - epoch, None)
            traced_batches = (len(tel.batch_sizes), None)
        if tracing is not None and i >= n and now >= warm_s + seconds:
            tracing.__exit__(None, None, None)
            tracing, traced = None, (traced[0], clock() - epoch)
            traced_batches = (traced_batches[0], len(tel.batch_sizes))
            # the stop writes the trace out, a stall of seconds: it comes
            # once every window request has been offered, so it delays
            # only the drain
            jax.profiler.stop_trace()
            profiling = False
        if i < n and due[i] <= now:
            with ann("submit"):
                submit[i] = now
                pending.add(i)
                rt.submit(reqs[i])
            i += 1
            collect()
            continue
        if i >= n and now > warm_s + seconds + drain_cap_s:
            break
        with ann("tick"):
            rt.tick(now * 1e6)
        collect()
        nxt = due[i] if i < n else now + 0.001
        if rt.queue:
            nxt = min(nxt, rt.queue[0].deadline / 1e6)
        wait = nxt - (clock() - epoch)
        if wait > 2e-4:
            with ann("wait"):
                time.sleep(wait)
    if tracing is not None:
        tracing.__exit__(None, None, None)
        traced = (traced[0], clock() - epoch)
        traced_batches = (traced_batches[0], len(tel.batch_sizes))
    if profiling:
        jax.profiler.stop_trace()
    if batch0 is None:
        batch0 = len(tel.batch_sizes)
    return Window(start=warm_s, end=warm_s + seconds, submit=submit,
                  done=done, batches=(batch0, len(tel.batch_sizes)),
                  trace_dir=trace_dir, traced=traced,
                  traced_batches=traced_batches, epoch=epoch)


def rerun(sys_: System, traffic: dict, seconds: float, seed: int, *,
          drain_cap_s: float = DRAIN_CAP_S) -> Window:
    """Another window on the same set-up: the caches emptied, the traffic
    drawn for ``seed``, its warm-up and window served as a run serves them.
    For the sweep and the control, which pay set-up once."""
    sys_.rt.reset()
    offer(sys_, traffic, seconds, seed)
    settle()
    return serve(sys_, float(traffic["warm_seconds"]), seconds,
                 drain_cap_s=drain_cap_s)


# -- the check ----------------------------------------------------------------
def check(sys_: System, win: Window, seed: int) -> dict:
    """Compare a seeded sample of the window's answers, every query class
    and cache path with its longest query in it, against the reference."""
    rt, reqs = sys_.rt, sys_.reqs
    in_win = np.flatnonzero((sys_.due_s >= win.start) & (sys_.due_s < win.end))
    strata: dict = {}
    for i in in_win.tolist():
        if i in rt.done_path:
            key = ("multi" if reqs[i].plen > 0 else "single",
                   rt.done_path[i])
            strata.setdefault(key, []).append(i)
    rng = np.random.default_rng((seed, 2))
    sample = []
    for key in sorted(strata):
        pool = strata[key]
        take = rng.choice(len(pool), min(N_CHECK, len(pool)), replace=False)
        picked = {pool[j] for j in take.tolist()}
        picked.add(max(pool, key=lambda j: len(reqs[j].query)))
        sample.extend(sorted(picked))
    mismatched = []
    for i in sample:
        r = reqs[i]
        want = sys_.ref.answer(r.query, r.k)
        got = np.asarray(rt._results[i])[: r.k]
        if not np.array_equal(got, want):
            mismatched.append(i)
            if len(mismatched) <= 5:
                log(f"MISMATCH {r.query!r} ({rt.done_path[i]}): served "
                    f"{got.tolist()} vs reference {want.tolist()}")
    unanswered = int(np.isnan(win.done[in_win]).sum())
    return {
        "strata": {f"{c}.{p}": len(v) for (c, p), v in sorted(strata.items())},
        "sample": sample,
        "checked": len(sample),
        "mismatched": len(mismatched),
        "unanswered": unanswered,
    }


# -- results ------------------------------------------------------------------
def latencies_ms(sys_: System, win: Window) -> np.ndarray:
    """Due time to answer on the host, for every request due in the window;
    an unanswered request reads +inf, so it misses every limit."""
    sel = (sys_.due_s >= win.start) & (sys_.due_s < win.end)
    lat = (win.done[sel] - sys_.due_s[sel]) * 1e3
    return np.where(np.isnan(lat), np.inf, lat)


def percentile(values, q: float) -> float:
    """``np.percentile``, as ``repro.obs.metrics.percentiles`` computes it."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(sys_: System, win: Window, setup_s: float) -> dict:
    """The end-to-end numbers a run can report; the cell's ``BENCHMARK.json``
    entries choose which. The latency percentiles are only logged: none
    holds steady enough from run to run for a bound (PERF.md)."""
    lat = latencies_ms(sys_, win)
    seconds = win.end - win.start
    return {
        "setup_s": setup_s,
        "p50_ms": percentile(lat, 50),
        "p95_ms": percentile(lat, 95),
        "p99_ms": percentile(lat, 99),
        "goodput_rps": float((lat <= SLA_MS).sum()) / seconds,
    }


def device_info(devs, *, peak: bool = True) -> dict:
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if peak:
        info["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs)
    return info


def is_correct(chk: dict) -> bool:
    return bool(chk["mismatched"] == 0 and chk["unanswered"] == 0
                and chk["checked"] > 0)


def checks_line(chk: dict) -> dict:
    """The numbers compared, each with its limit (the result's last key)."""
    return {"mismatched_answers": {"value": chk["mismatched"], "limit": 0},
            "unanswered_requests": {"value": chk["unanswered"], "limit": 0}}


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, root: Path = ROOT, require_tpu: bool = True,
            wrap_frontend=None, trace_dir: str | None = None):
    """One run of one cell -> (result, system, window, check)."""
    import jax

    cell = load_cell(cell_name, root)
    if require_tpu:
        devs = tpu_devices(cell.chips)
    else:
        devs = jax.devices()[: cell.chips]
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    warm_s = float(cell.traffic["warm_seconds"])
    sys_ = build_system(cell, wrap_frontend=wrap_frontend)
    offer(sys_, cell.traffic, seconds, seed)
    warm_variants(sys_)
    settle()
    if trace:
        trace_dir = trace_dir or str(root / "bench_out" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    if trace:
        # tracing every device op slows the engines several times over, so
        # a traced run's window is the traced stretch alone: what would
        # follow it measures the tracer, and its backlog
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
    win = serve(sys_, warm_s, seconds, trace_s=seconds if trace else 0,
                trace_dir=trace_dir if trace else None)
    setup_s = win.epoch + warm_s - t_start
    e2e = end_to_end(sys_, win, setup_s)
    device = device_info(devs)
    t0 = time.perf_counter()
    chk = check(sys_, win, seed)
    log(f"check: {chk['checked']} sampled answers over strata "
        f"{chk['strata']} against the reference in "
        f"{time.perf_counter() - t0:.1f}s")
    lat = latencies_ms(sys_, win)
    result = {
        "correct": is_correct(chk),
        "attempted": int(lat.size),
        "failed": int(chk["unanswered"]),
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        from .traces import reduce_trace

        red = reduce_trace(win, sys_)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = RunContext(sys_=sys_, win=win, trace=red,
                         device_kind=devs[0].device_kind)
        values = {m["name"]: load_reader(m["name"])(ctx)
                  for m in cell.per_layer}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if v is not None}
        result["device"] = device
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    log(f"window: {int(lat.size)} requests due, p50 {e2e['p50_ms']:.3f} ms, "
        f"p95 {e2e['p95_ms']:.3f} ms, p99 {e2e['p99_ms']:.3f} ms, goodput "
        f"{e2e['goodput_rps']:.3f} req/s, setup {setup_s:.1f}s, "
        f"{len(sys_.auditor.violations)} compiles in the window")
    result["checks"] = checks_line(chk)
    return result, sys_, win, chk


def run(*args, **kwargs) -> dict:
    """One run of one cell -> the result object (the last stdout line)."""
    return execute(*args, **kwargs)[0]


@dataclasses.dataclass
class RunContext:
    """What a per-layer reader reads: the set-up, the window, and the
    reduced trace. In a traced run the window is the traced stretch."""

    sys_: System
    win: Window
    trace: object
    device_kind: str

    @property
    def bounds(self) -> tuple:
        """(start, end) in seconds from the traffic epoch."""
        return self.win.traced or (self.win.start, self.win.end)

    @property
    def batches(self) -> tuple:
        """(first, last) index into ``RuntimeTelemetry.batch_sizes``."""
        return self.win.traced_batches or self.win.batches

    def window_mask(self) -> np.ndarray:
        """The requests due inside ``bounds``."""
        due = self.sys_.due_s
        t0, t1 = self.bounds
        return (due >= t0) & (due < t1)

"""Driver ``serve``: one LLM server behind ``tensor_llm_serversink``, driven
in-process and timed per token.

The entry the window drives is one pipeline of two chains,

    appsrc name=in ! tensor_llm_serversink id=bench stream=true <server props>
    tensor_llm_serversrc id=bench stream=true ! tensor_sink name=out

started with ``.start()``. The generator pushes prompt frames (meta
``max_new_tokens``) into ``in``; a ``new-data`` callback on ``out`` stamps
every token frame. Everything the cell fixes is data: the configuration's
file gives the model string and the server properties, the mix's file the
traffic, the cell's file (optional) what belongs to the pair: the offered
rate and the limits of the correctness numbers.
"""

from __future__ import annotations

import gc
import os
import queue
import shutil
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.lib import check, shapes, traffic, xplane
from benchmark.lib.compiles import Compiles
from benchmark.lib.stats import percentile

DRAIN_S = 60.0       # wait this long past the window's close for answers
WARM_NEW = 12        # tokens per warm-up request: two pump launches
FAULT_KEYS = ("device_degraded", "device_faults", "device_circuit_opens",
              "device_eager_invokes", "chain_fallback_windows", "oom_events")


class Recorder:
    """``new-data`` callback of the out sink: stamps token and done frames."""

    def __init__(self) -> None:
        self.tok_t: Dict[int, List[float]] = {}
        self.done_t: Dict[int, float] = {}
        self.tokens: Dict[int, np.ndarray] = {}
        self.finished: "queue.Queue[int]" = queue.Queue()

    def __call__(self, frame) -> None:
        now = time.perf_counter()
        meta = frame.meta
        rid = meta.get("bench_rid")
        if rid is None:
            return
        if meta.get("done"):
            self.tokens[rid] = np.asarray(frame.tensors[0]).reshape(-1).astype(np.int32)
            self.done_t[rid] = now
            self.finished.put(rid)
        else:
            self.tok_t.setdefault(rid, []).append(now)


class Load:
    """The one load thread. Open loop: push each request when it is due,
    and record how late and how long the push blocked. Closed loop: keep
    ``clients`` requests outstanding until told to stop."""

    def __init__(self, src, rec: Recorder, make_frame):
        self.src, self.rec, self.make_frame = src, rec, make_frame
        self.sent: Dict[int, dict] = {}   # rid -> {due, push_t, prompt, out_len}
        self.late_ms: List[float] = []
        self.block_ms: List[float] = []
        self.stop = threading.Event()
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def _push(self, req, due_abs: Optional[float]) -> None:
        t0 = time.perf_counter()
        self.sent[req.rid] = {"due": due_abs if due_abs is not None else t0,
                              "push_t": t0, "prompt": req.prompt,
                              "out_len": req.out_len}
        self.src.push(self.make_frame(req))
        t1 = time.perf_counter()
        if due_abs is not None:
            self.late_ms.append((t0 - due_abs) * 1e3)
        self.block_ms.append((t1 - t0) * 1e3)

    def run_open(self, schedule, t_open: float) -> None:
        def body():
            try:
                for req in schedule:
                    due = t_open + req.due
                    while not self.stop.is_set():
                        wait = due - time.perf_counter()
                        if wait <= 0:
                            break
                        time.sleep(min(wait, 0.05))
                    if self.stop.is_set():
                        return
                    self._push(req, due)
            except BaseException as exc:  # noqa: BLE001 — surfaced by the driver
                self.error = exc

        self.thread = threading.Thread(target=body, name="bench-load", daemon=True)
        self.thread.start()

    def run_closed(self, gen: "traffic.ClosedLoop") -> None:
        def body():
            try:
                for _ in range(gen.clients):
                    if self.stop.is_set():
                        return
                    self._push(gen.next(), None)
                while not self.stop.is_set():
                    try:
                        self.rec.finished.get(timeout=0.05)
                    except queue.Empty:
                        continue
                    if not self.stop.is_set():
                        self._push(gen.next(), None)
            except BaseException as exc:  # noqa: BLE001
                self.error = exc

        self.thread = threading.Thread(target=body, name="bench-load", daemon=True)
        self.thread.start()

    def join(self) -> None:
        self.stop.set()
        if self.thread is not None:
            self.thread.join(timeout=30.0)


def _device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def _load_hook(config_name: str) -> None:
    """A configuration may bring a hook module beside its file
    (``configs/<name>.py``; the file may name another's with ``"hook"``), e.g. to
    register a zoo factory."""
    import importlib.util

    path = os.path.join(shapes.HERE, "configs", config_name + ".py")
    if os.path.exists(path):
        spec = importlib.util.spec_from_file_location(
            "benchmark_config_" + config_name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)


def build(config: dict, wseed: int):
    """The pipeline of the two chains, from the configuration's file."""
    from nnstreamer_tpu.elements.llm_serve import LlmServerSink, LlmServerSrc
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    props = dict(config["server"])
    props["custom"] = config["custom"].format(seed=wseed)
    props["model"] = config["model"]
    props["id"] = "bench"
    props["stream"] = True
    src = AppSrc(name="in", spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
    out = TensorSink(name="out", **{"max-stored": 1})
    pipe = Pipeline().chain(src, LlmServerSink(name="llm", **props))
    pipe.chain(LlmServerSrc(name="llmsrc", id="bench", stream=True), out)
    return pipe, src, out


def _health(ex) -> List[str]:
    bad = []
    for node, row in ex.stats().items():
        for key in FAULT_KEYS:
            if row.get(key):
                bad.append(f"{node}:{key}={row.get(key)}")
    bad += [f"executor error: {e!r}" for e in ex.errors]
    return bad


def _live_kv_tokens(reqs: Dict[int, dict], rec: Recorder, lo: float, hi: float,
                    n: int = 64) -> float:
    """Mean, over n instants of [lo, hi), of the tokens whose keys and
    values a decode step at that instant must read: for every request that
    has its first token and is not done, prompt + tokens so far."""
    total = 0.0
    instants = lo + (np.arange(n) + 0.5) / n * (hi - lo)
    for rid, r in reqs.items():
        ts = rec.tok_t.get(rid)
        if not ts:
            continue
        end = rec.done_t.get(rid, float("inf"))
        arr = np.asarray(ts)
        for t in instants:
            if ts[0] <= t < end:
                total += len(r["prompt"]) + int(np.searchsorted(arr, t, "right"))
    return total / n


class _Trace:
    """The profiler around part of the window, with the two marks that bound
    it on the trace's clock and the batcher's counters at both ends."""

    def __init__(self, out_dir: str, start_at: float, length: float, stats):
        self.dir = os.path.join(out_dir, "trace")
        self.start_at, self.length, self.stats = start_at, length, stats
        self.lo = self.hi = None
        self.c0 = self.c1 = None

    def tick(self, now: float) -> None:
        import jax

        if self.lo is None and now >= self.start_at:
            shutil.rmtree(self.dir, ignore_errors=True)  # keep one trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.c0 = self.stats()
            self.lo = time.perf_counter()
            with jax.profiler.TraceAnnotation(xplane.MARK_BEGIN):
                pass
        elif self.lo is not None and self.hi is None and now >= self.lo + self.length:
            self.end()

    def end(self) -> None:
        import jax

        if self.lo is None or self.hi is not None:
            return
        with jax.profiler.TraceAnnotation(xplane.MARK_END):
            pass
        self.hi = time.perf_counter()
        self.c1 = self.stats()
        jax.profiler.stop_trace()


def _warm_up(src, rec: Recorder, ex, make_frame, config: dict, mix: dict,
             vocab: int, seed: int) -> set:
    """Every shape the window will use, and every slot once: one prompt of
    the longest kind the mix sends (through the chunk programs where it
    exceeds the bucket) and n-slots + 1 short ones."""
    plen = int(config["server"]["prompt-len"])
    n_slots = int(config["server"]["n-slots"])
    rng = np.random.default_rng(seed + 1)
    lens = [min(int(mix["prompt_len"]["hi"]), 2 * plen + plen // 2)]
    lens += [max(1, plen // 2)] * (n_slots + 1)
    for i, n in enumerate(lens):
        src.push(make_frame(traffic.Request(
            -1 - i, None, rng.integers(0, vocab, (n,)).astype(np.int32), WARM_NEW)))
    t0 = time.perf_counter()
    while len(rec.done_t) < len(lens):
        if ex.errors:
            raise ex.errors[0]
        if time.perf_counter() - t0 > 1100:
            raise SystemExit("warm-up did not finish in 1100 s")
        time.sleep(0.02)
    while not rec.finished.empty():
        rec.finished.get_nowait()
    return set(rec.done_t)


def _latencies(in_window: Dict[int, dict], rec: Recorder, worst_ms: float):
    """Per request due in the window: (ttft, tpot or None, e2e) in ms, timed
    from when it was due; one that never finished counts as the worst."""
    out = {}
    for rid, r in in_window.items():
        ts = rec.tok_t.get(rid)
        if rid not in rec.done_t or not ts:
            out[rid] = (worst_ms, worst_ms, worst_ms)
            continue
        tpot = (ts[-1] - ts[0]) * 1e3 / (len(ts) - 1) if len(ts) > 1 else None
        out[rid] = ((ts[0] - r["due"]) * 1e3, tpot,
                    (rec.done_t[rid] - r["due"]) * 1e3)
    return out


def _traced_work(sizes: dict, sent: Dict[int, dict], rec: Recorder,
                 lo: float, hi: float) -> dict:
    """Model FLOPs and tokens of the work whose tokens came in [lo, hi):
    a prompt counts where its first token came, an output token where it came."""
    first_in = [r for rid, r in sent.items()
                if rec.tok_t.get(rid) and lo <= rec.tok_t[rid][0] < hi]
    decode_flops, out_tokens = 0.0, 0
    for rid, r in sent.items():
        for j, t in enumerate(rec.tok_t.get(rid, ())):
            if j > 0 and lo <= t < hi:  # token 0 is the prefill's
                decode_flops += shapes.flops_token(sizes, len(r["prompt"]) + j, True)
                out_tokens += 1
    return {
        "prefill_flops": sum(shapes.flops_prompt(sizes, len(r["prompt"]))
                             for r in first_in),
        "prompt_tokens": sum(len(r["prompt"]) for r in first_in),
        "decode_flops": decode_flops, "out_tokens": out_tokens,
        "live_kv_tokens": _live_kv_tokens(sent, rec, lo, hi),
    }


def run(cell: dict, config: dict, mix: dict, cellfile: dict, args, *,
        require_chip: bool = True, control=(), log=print) -> dict:
    """One run of one cell; returns the result line as a dict."""
    t_start = args.t0
    import jax

    devices = jax.devices()
    info = _device_info(devices)
    if require_chip and info["platform"] != "tpu":
        raise SystemExit(f"no accelerator: jax reports platform {info['platform']!r}")
    if len(devices) < int(cell.get("chips", 1)):
        raise SystemExit(f"cell asks for {cell['chips']} chips, jax has {len(devices)}")
    devices = devices[: int(cell.get("chips", 1))]
    info["count"] = len(devices)
    comp = Compiles()
    from nnstreamer_tpu.obs import metrics as obs_metrics
    from nnstreamer_tpu.tensors.frame import Frame

    obs = obs_metrics.enable()
    _load_hook(config.get("hook", cell["config"]))
    sizes = shapes.sizes_of(config)
    seed = int(args.seed)
    wseed = seed % (2 ** 31 - 1)  # the weights' seed: fits any int32 parser
    mix = {**mix, **cellfile.get("traffic", {})}
    n_slots = int(config["server"]["n-slots"])
    max_len = int(config["server"]["max-len"])
    seconds = float(args.seconds)

    pipe, src, out = build(config, wseed)
    rec = Recorder()
    out.connect("new-data", rec)
    ex = pipe.start()
    stats = pipe["llmsrc"].serving_stats

    def make_frame(req):
        return Frame((req.prompt[None, :],),
                     meta={"max_new_tokens": int(req.out_len), "bench_rid": req.rid})

    warm_ids = _warm_up(src, rec, ex, make_frame, config, mix, sizes["vocab"], seed)
    log(f"[bench] warm-up done at {time.perf_counter() - t_start:.2f} s: "
        f"{comp.snapshot()}")

    # -- load: ramp (set-up), then the window -------------------------------
    load = Load(src, rec, make_frame)
    t_open = time.perf_counter() + float(mix.get("ramp_s", 0.0))
    t_close = t_open + seconds
    if mix["arrivals"] == "open_poisson":
        load.run_open(traffic.open_schedule(mix, seed, seconds, sizes["vocab"]), t_open)
    elif mix["arrivals"] == "closed_loop":
        load.run_closed(traffic.ClosedLoop(mix, seed, n_slots, sizes["vocab"]))
    else:
        raise SystemExit(f"unknown arrivals {mix['arrivals']!r}")
    kv_gauge = obs.find("nns_kv_blocks_in_use")
    kv_total = int(stats().get("kv_blocks", 0))
    time.sleep(max(0.0, t_open - time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    programs_open = comp.programs
    trace = None
    if int(args.trace):
        trace = _Trace(args.out_dir, t_open + min(2.0, seconds * 0.2),
                       float(cellfile.get("trace_s", min(5.0, seconds * 0.5))), stats)
    kv_peak = 0
    while time.perf_counter() < t_close:
        if kv_gauge is not None:
            kv_peak = max(kv_peak, int(kv_gauge.value))
        if trace is not None:
            trace.tick(time.perf_counter())
        if load.error is not None:
            raise load.error
        if ex.errors:
            raise ex.errors[0]
        time.sleep(min(0.02, max(0.0, t_close - time.perf_counter())))
    if trace is not None:
        trace.end()
    compiles_in_window = comp.programs - programs_open
    load.join()

    # -- wait for every answer that is due ---------------------------------
    sent = dict(load.sent)
    t_drain = time.perf_counter()
    while any(r not in rec.done_t for r in sent):
        if ex.errors or time.perf_counter() - t_drain > DRAIN_S:
            break
        time.sleep(0.02)
    drain_s = time.perf_counter() - t_drain
    stats_end = {k: v for k, v in stats().items() if k != "requests"}
    memory_peak = _peak_bytes(devices)
    faults = _health(ex)

    # -- stop the program and free its state -------------------------------
    if all(r in rec.done_t for r in sent):
        src.end_of_stream()  # an orderly end; a stuck queue would block here
        ex.wait(20.0)
    ex.stop()
    leaked = list(ex.leaked_threads or [])
    load.src = None
    del pipe, src, out, ex, stats
    if trace is not None:
        trace.stats = None
    gc.collect()

    # -- end-to-end numbers -------------------------------------------------
    in_window = {rid: r for rid, r in sent.items() if t_open <= r["due"] < t_close}
    attempted = len(in_window)
    bad = {rid for rid, r in in_window.items()
           if rid not in rec.done_t or len(rec.tokens[rid]) != r["out_len"]}
    failed = len(bad)
    lat = _latencies(in_window, rec, (t_drain + DRAIN_S - t_open) * 1e3)
    ttft = [v[0] for v in lat.values()]
    tpot = [v[1] for v in lat.values() if v[1] is not None]
    e2e = [v[2] for v in lat.values()]
    tokens_in_window = sum(
        1 for rid, ts in rec.tok_t.items() if rid not in warm_ids
        for t in ts if t_open <= t < t_close)
    numbers = {
        "out_tok_per_s": tokens_in_window / seconds,
        "tpot_p95_ms": percentile(tpot, 95) if tpot else None,
        "e2e_p95_ms": percentile(e2e, 95) if e2e else None,
        "setup_s": setup_s,
    }
    log(f"[bench] window closed: attempted {attempted} failed {failed} "
        f"tokens {tokens_in_window} drain {drain_s:.2f} s setup {setup_s:.2f} s "
        f"compiles {comp.snapshot()} peak {memory_peak / 1e9:.2f} GB")
    log(f"[bench] batcher {stats_end}")
    if ttft:
        half = t_open + seconds / 2
        h1 = [lat[rid][0] for rid, r in in_window.items() if r["due"] < half]
        h2 = [lat[rid][0] for rid, r in in_window.items() if r["due"] >= half]
        backlog = sum(1 for rid, r in sent.items() if r["push_t"] < t_close
                      and rec.done_t.get(rid, float("inf")) >= t_close)
        log(f"[bench] outstanding at close {backlog}; ttft p50 first half "
            f"{percentile(h1 or [0], 50):.1f} ms, second half "
            f"{percentile(h2 or [0], 50):.1f} ms; mean/p50/p90/p95/max "
            f"{sum(ttft) / len(ttft):.1f}/{percentile(ttft, 50):.1f}/"
            f"{percentile(ttft, 90):.1f}/{percentile(ttft, 95):.1f}/{max(ttft):.1f}")
    if load.late_ms:
        log(f"[bench] generator late p50/p95/max ms: "
            f"{percentile(load.late_ms, 50):.2f}/{percentile(load.late_ms, 95):.2f}/"
            f"{max(load.late_ms):.2f}; push block p95 {percentile(load.block_ms, 95):.2f}")

    # -- the comparison ------------------------------------------------------
    finished = [{"rid": rid, "prompt": r["prompt"], "tokens": rec.tokens[rid]}
                for rid, r in in_window.items() if rid not in bad]
    sample = check.draw_sample(finished, seed)
    t_ref = time.perf_counter()
    refspec = config["reference"]
    cmp_numbers = check.gaps(refspec, sizes, wseed, sample, max_len)
    ref_s = time.perf_counter() - t_ref
    cmp_numbers.update({
        "unanswered": float(failed),
        "compiles_in_window": float(compiles_in_window),
        "device_faults": float(len(faults)),
    })
    limits = cellfile.get("limits", {})
    checks = check.judge(cmp_numbers, limits)
    log(f"[bench] reference took {ref_s:.2f} s over {len(sample)} requests; "
        f"numbers {cmp_numbers}; faults {faults}; leaked threads {leaked}")

    result = {
        "correct": bool(checks) and all(c["ok"] for c in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": {**info, "memory_peak_bytes": memory_peak},
    }
    reported = args.metrics_for(cell["name"], trace is not None)
    if trace is None:
        values = numbers
    else:
        red = xplane.reduce_trace(xplane.find_xplane(trace.dir),
                                  allow_host=not require_chip)
        ctx = {
            "trace": red, "sizes": sizes,
            # a rehearsal on the host has no peaks: its readers of a share
            # of a peak find nothing to read
            "peaks": shapes.load_peaks(info["kind"]) if require_chip else None,
            "n_slots": n_slots, "pump": int(config["server"].get("pump", 1)),
            "trace_names": config.get("trace_names", {}),
            "counters": {k: trace.c1.get(k, 0) - trace.c0.get(k, 0)
                         for k in ("steps", "tokens_emitted", "kv_prefill_chunks")},
            "kv_blocks_total": kv_total, "kv_blocks_peak": kv_peak,
            "gen_late_ms": load.late_ms, "push_block_ms": load.block_ms,
            "ttft_ms": ttft, "host_window_s": trace.hi - trace.lo,
            **_traced_work(sizes, sent, rec, trace.lo, trace.hi),
        }
        values = {m["name"]: args.read_layer_metric(m["name"], ctx) for m in reported}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = _breakdown(red, sent, rec, trace.lo)
        quiet = ("trace", "gen_late_ms", "push_block_ms", "ttft_ms")
        log(f"[bench] trace: marks {red['marks_found']} window {red['window_s']:.3f} s "
            f"(host {ctx['host_window_s']:.3f} s) busy {red['busy_s']:.3f} s "
            f"lines {red['lines']}")
        by_time = sorted(red["modules"].items(), key=lambda kv: -kv[1][0])[:12]
        log(f"[bench] modules [name, seconds, launches] "
            f"{[[k, v[0], v[1]] for k, v in by_time]}")
        log(f"[bench] ctx { {k: v for k, v in ctx.items() if k not in quiet} }")
    for m in reported:
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["setup"] = {**comp.snapshot(), "setup_s": setup_s, "reference_s": ref_s,
                       "drain_s": drain_s, "seed": seed}
    if control:  # each lower precision in the program's place, by the same limits
        result["controls"] = {}
        for name in refspec["controls"] if "all" in control else control:
            nums = check.gaps(refspec, sizes, wseed, sample, max_len, control=name)
            judged = check.judge(nums, {k: v for k, v in limits.items() if k in nums})
            result["controls"][name] = {
                "correct": all(c["ok"] for c in judged), "checks": judged}
            log(f"[bench] control {name}: correct="
                f"{result['controls'][name]['correct']} {nums}")
    result["checks"] = checks
    return result


def _breakdown(red: dict, reqs: Dict[int, dict], rec: Recorder, host_lo: float) -> dict:
    """Top device ops and modules, and the idle gaps by what the benchmark
    itself knows of the host at that time (the program carries no
    annotation, so a gap has no finer cause yet)."""
    lo_ns, hi_ns = red["window_ns"]
    ops = {**{"module:" + k: v[0] for k, v in red["modules"].items()}, **red["ops"]}
    classes: Dict[str, float] = {}
    for a, b in xplane.gaps(red["busy_intervals_ns"], lo_ns, hi_ns):
        t = host_lo + ((a + b) / 2 - lo_ns) * 1e-9
        outstanding = [rid for rid, r in reqs.items()
                       if r["push_t"] <= t and rec.done_t.get(rid, float("inf")) > t]
        decoding = [rid for rid in outstanding
                    if rec.tok_t.get(rid) and rec.tok_t[rid][0] <= t]
        if not outstanding:
            name = "no_request_outstanding"
        elif not decoding:
            name = "host_before_first_token"
        else:
            name = "host_between_launches"
        classes[name] = classes.get(name, 0.0) + (b - a) * 1e-9
    return {"device_ops": xplane.top(ops, 10), "idle_gaps": xplane.top(classes, 10)}

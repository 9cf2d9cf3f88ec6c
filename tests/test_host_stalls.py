"""The stall sentinel (nnstreamer_tpu/trace.py ``StallSentinel``): the rule that
tells the machine's freezes from the process's own, a GIL hold read as a
``process`` stall of its length, the sentinel's life inside the LLM server
(only under metrics or a Tracer, joined when the pipeline stops), and the
benchmark's readers of ``nns.host.*`` and ``gap_ms`` on synthetic events.
"""

import subprocess
import sys
import threading
import time

import pytest

from benchmark.layer_metrics import decode_gap_max_ms, decode_gap_stalled_pct, host_stall_pct
from benchmark.lib import host_spans
from nnstreamer_tpu import trace
from nnstreamer_tpu.obs import metrics as obs_metrics

SENTINEL = "nns-stall-sentinel"


@pytest.mark.parametrize("late_ms,os_late_ms,cpu_ms,kind", [
    (110.0, 109.0, 120.0, "machine"),  # the OS did not run the thread: a freeze
    (110.0, 55.0, 120.0, "machine"),   # half of it
    (110.0, 54.0, 120.0, "process"),   # under half, and the process ran: the lock
    (110.0, 0.0, 30.0, "machine"),     # woken, then nothing of ours ran either
    (110.0, 0.0, 55.0, "process"),     # half the stall on a CPU: a thread of ours
    (80.0, 0.0, 80.0, "process"),      # woken on time, then a GIL hold
    (20.0, 20.0, 0.0, "machine"),
])
def test_stall_kind_is_machine_where_the_os_woke_late_or_nothing_ran(
        late_ms, os_late_ms, cpu_ms, kind):
    assert trace.stall_kind(late_ms, os_late_ms, cpu_ms) == kind


def _hold_gil(seconds: float):
    """Keep the GIL on this thread for ``seconds`` of busy work; -> the hold's
    start (``perf_counter``), wall seconds and this thread's CPU seconds."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    try:
        t0, c0 = time.perf_counter(), time.thread_time()
        while time.perf_counter() - t0 < seconds:
            pass
        return t0, time.perf_counter() - t0, time.thread_time() - c0
    finally:
        sys.setswitchinterval(old)


def _stalls_over(tr, t: float):
    """The ``nns.host.stall`` instants whose stall [event - late_ms, event]
    holds the instant ``t`` (``time.perf_counter()``)."""
    at = tr._ts_us(t)
    return [e["args"] for e in tr.events() if e["name"] == "nns.host.stall"
            and e["ts"] - e["args"]["late_ms"] * 1e3 <= at <= e["ts"]]


def test_a_gil_hold_is_a_process_stall_of_its_length():
    reg = obs_metrics.MetricsRegistry()
    tr = trace.enable()
    try:
        s = trace.StallSentinel(reg)
        for _ in range(10):  # a hold the machine let run: a loaded host may not
            time.sleep(0.05)
            t0, hold, cpu = _hold_gil(0.08)
            if cpu >= 0.75 * hold:
                break
        hold_ms = hold * 1e3
        time.sleep(0.05)
        s.stop()
        over = _stalls_over(tr, t0 + hold / 2)
        stalls = [e["args"] for e in tr.events() if e["name"] == "nns.host.stall"]
    finally:
        trace.disable()
    tick = trace.STALL_TICK_S * 1e3
    assert len(over) == 1, (stalls, hold_ms)
    (a,) = over
    assert abs(a["late_ms"] - hold_ms) <= tick + 2.0, (a, hold_ms)  # within a tick
    # woken on time, then the lock; the sleep spans the hold and its CPU
    assert a["os_late_ms"] < 0.5 * a["late_ms"], a
    assert a["cpu_ms"] >= cpu * 1e3 - 1.0, (a, cpu)
    if cpu >= 0.75 * hold:  # the process ran through the hold: its own stall
        assert a["kind"] == "process", (a, hold_ms, cpu)
    assert s.stalls >= 1 and s.stall_ms >= a["late_ms"] - 1e-3  # the instant rounds
    for kind in ("process", "machine"):
        lates = [x["late_ms"] for x in stalls if x["kind"] == kind]
        counted = reg.find("nns_host_stalls_total", kind=kind)
        assert (counted.value if counted else 0) == len(lates)
        if lates:
            assert reg.find("nns_host_stall_ms", kind=kind).max == pytest.approx(
                max(lates), abs=1e-3)
    assert s.stalls == len(stalls)


def test_a_stopped_process_is_a_machine_stall_of_its_length():
    """SIGSTOP from another process stops every thread of this one, as a
    frozen machine does: the kernel wakes the sentinel late, or (where the
    stop found it already woken) nothing of the process ran over it."""
    tr = trace.enable()
    try:
        s = trace.StallSentinel()
        helper = subprocess.Popen([sys.executable, "-c", (
            "import os, signal, time\n"
            "time.sleep(0.1)\n"
            "t0 = time.perf_counter(); os.kill(os.getppid(), signal.SIGSTOP)\n"
            "time.sleep(0.08); os.kill(os.getppid(), signal.SIGCONT)\n"
            "print(t0, time.perf_counter())")], stdout=subprocess.PIPE, text=True)
        out, _ = helper.communicate(timeout=30)
        time.sleep(0.05)
        s.stop()
        t0, t1 = map(float, out.split())  # the same CLOCK_MONOTONIC as ours
        over = _stalls_over(tr, (t0 + t1) / 2)
        stalls = [e["args"] for e in tr.events() if e["name"] == "nns.host.stall"]
    finally:
        trace.disable()
    stop_ms = (t1 - t0) * 1e3
    tick = trace.STALL_TICK_S * 1e3
    assert len(over) == 1, (stalls, stop_ms)
    (a,) = over
    assert a["kind"] == "machine", (a, stop_ms)
    # the helper's clock brackets the stop from outside, its own delays included
    assert 0.75 * stop_ms <= a["late_ms"] <= 1.25 * stop_ms + tick, (a, stop_ms)
    assert s.stalls_machine >= 1


def test_the_heartbeat_counts_the_sleeps(monkeypatch):
    monkeypatch.setattr(trace, "WATCH_EVERY_S", 0.1)
    tr = trace.enable()
    try:
        s = trace.StallSentinel()
        time.sleep(0.35)
        s.stop()
        s.stop()  # idempotent
        beats = [e["args"] for e in tr.events() if e["name"] == "nns.host.watch"]
    finally:
        trace.disable()
    assert len(beats) >= 2
    assert all(b["ticks"] >= 1 and b["late_max_ms"] >= 0.0 for b in beats)
    assert not s._thread.is_alive()


# -- the sentinel inside the LLM server --------------------------------------

def _sentinels():
    return [t for t in threading.enumerate() if t.name == SENTINEL and t.is_alive()]


def _server_pipeline(srv_id: str):
    from nnstreamer_tpu.elements.llm_serve import LlmServerSink, LlmServerSrc
    from nnstreamer_tpu.elements.sink import AppSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    src = AppSrc(spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
    sink = LlmServerSink(**{
        "id": srv_id, "model": "zoo:transformer_lm",
        "custom": "vocab:97,d_model:16,n_heads:2,n_layers:1,seed:1",
        "n-slots": 2, "max-len": 32, "prompt-len": 8, "max-new-tokens": 4,
        "kv-layout": "paged", "block-size": 8})
    out = LlmServerSrc(**{"id": srv_id})
    p = Pipeline().chain(src, sink)
    p.chain(out, AppSink())
    return p, src, out


@pytest.fixture
def quiet(monkeypatch):
    """Neither the metrics registry nor a Tracer, whatever ran before."""
    monkeypatch.setattr(obs_metrics, "_registry", None)
    monkeypatch.delenv("NNS_TPU_METRICS", raising=False)
    monkeypatch.delenv("NNS_TPU_METRICS_PORT", raising=False)
    monkeypatch.setattr(trace, "_tracer", None)
    monkeypatch.setattr(trace, "_env_checked", True)


def test_no_sentinel_without_metrics_or_a_tracer(quiet):
    before = len(_sentinels())
    p, _, out = _server_pipeline("stall-quiet")
    p.start()
    try:
        assert len(_sentinels()) == before
        assert "host_stalls" not in out.serving_stats()
    finally:
        p.stop()


def test_the_sentinel_is_joined_when_the_pipeline_stops(quiet, monkeypatch):
    monkeypatch.setattr(obs_metrics, "_registry", obs_metrics.MetricsRegistry())
    before = set(_sentinels())
    p, _, out = _server_pipeline("stall-watched")
    p.start()
    try:
        mine = set(_sentinels()) - before
        assert len(mine) == 1
        st = out.serving_stats()
        assert {"host_stalls", "host_stall_ms", "host_stalls_machine"} <= set(st)
    finally:
        p.stop()
    (t,) = mine
    assert not t.is_alive()
    assert set(_sentinels()) <= before
    assert "host_stalls" in out.serving_stats()  # the final snapshot keeps them


# -- the benchmark's readers, on synthetic events -----------------------------

MS = 1e6  # ns


def _ctx(events, window=(0.0, 1000 * MS)):
    ctx = {"trace": {"window_ns": window, "window_s": (window[1] - window[0]) * 1e-9,
                     "busy_intervals_ns": []}}
    host_spans._parsed[tuple(window)] = {"launches": [], "events": [
        {"name": n, "line": "l", "start_ns": s, "end_ns": e, "stats": st}
        for n, s, e, st in events]}
    return ctx


def _stall(at_ms, late_ms, kind="machine"):
    return ("nns.host.stall", at_ms * MS, at_ms * MS, {"late_ms": late_ms, "kind": kind})


def _harvest(at_ms, gap_ms=None):
    return ("nns.pump.harvest", at_ms * MS, at_ms * MS + 0.1 * MS,
            {} if gap_ms is None else {"gap_ms": gap_ms})


WATCH = ("nns.host.watch", 500 * MS, 500 * MS, {"ticks": 190, "late_max_ms": 3.0})


@pytest.fixture
def parsed():
    yield
    host_spans._parsed.clear()


def test_host_stall_pct_reads_machine_stalls_clipped_to_the_window(parsed):
    ctx = _ctx([WATCH, _stall(300, 100), _stall(600, 80, "process"),
                _stall(50, 100),              # began 50 ms before the window
                _stall(290, 40)])             # inside the first: counted once
    assert host_stall_pct.read(ctx) == pytest.approx(15.0)


def test_host_stall_pct_is_none_without_a_heartbeat(parsed):
    assert host_stall_pct.read(_ctx([_stall(300, 100)])) is None   # the parent
    assert host_stall_pct.read(_ctx([WATCH])) == 0.0


def test_decode_gap_max_ms_is_the_largest_gap(parsed):
    ctx = _ctx([_harvest(10), _harvest(74, 64.0), _harvest(245, 171.0), _harvest(309, 64.0)])
    assert decode_gap_max_ms.read(ctx) == pytest.approx(171.0)
    assert decode_gap_max_ms.read(_ctx([_harvest(10), _harvest(74)])) is None  # the parent


def test_decode_gap_stalled_pct_splits_the_long_gaps_excess(parsed):
    # gaps 64, 64, 64, a prompt program's 110 and a freeze's 170 of which the
    # machine held 100 ms: median 64, excess 46 + 106, stalled min(100, 106)
    events = [_harvest(64, 64.0), _harvest(128, 64.0), _harvest(192, 64.0),
              _harvest(302, 110.0), _harvest(472, 170.0), _stall(450, 100),
              _stall(250, 30, "process")]
    assert decode_gap_stalled_pct.read(_ctx(events)) == pytest.approx(100.0 * 100 / 152)


def test_decode_gap_stalled_pct_is_zero_without_a_long_gap(parsed):
    assert decode_gap_stalled_pct.read(_ctx([_harvest(64, 64.0), _harvest(130, 66.0)])) == 0.0
    assert decode_gap_stalled_pct.read(_ctx([_harvest(64), _stall(30, 50)])) is None  # parent

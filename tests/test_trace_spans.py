"""Program spans on the profiler's clock (nnstreamer_tpu/trace.py ``span`` /
``instant``): a tiny paged batcher behind ``tensor_llm_serversink`` runs under
``jax.profiler.start_trace`` on the CPU backend and the ``.xplane.pb`` is read
back with ``ProfileData`` — every cataloged span of the serving path appears,
nests and orders as docs/observability.md says, attributes round-trip, and the
token stream does not depend on whether anything is listening. Plus the pure
reduction the benchmark applies to those spans (benchmark/lib/host_spans.py).
"""

import glob
import os

import numpy as np
import pytest

from benchmark.lib import host_spans
from nnstreamer_tpu import trace

MODEL_OPTS = "vocab:211,d_model:32,n_heads:2,n_layers:2,seed:5"
PUMP = 4
NEW = 9            # tokens per request: the prefill's, then two pumps
N_SLOTS = 2
PROMPT_LENS = (5, 21, 9, 14, 7)   # 21 > prompt-len: the chunk programs run
PUMP_PHASES = ("nns.pump.prefill", "nns.pump.admit", "nns.pump.prepare",
               "nns.pump.launch", "nns.pump.wait", "nns.pump.harvest")
REQ_ORDER = ("nns.req.submit", "nns.req.prefill_start", "nns.req.admitted",
             "nns.req.first_token", "nns.req.done")


def _serve(srv_id: str):
    """Five requests through two slots (so submit back-pressures), streamed;
    -> {request index: [tokens in arrival order]}."""
    from nnstreamer_tpu.elements.llm_serve import LlmServerSink, LlmServerSrc
    from nnstreamer_tpu.elements.sink import AppSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.tensors.frame import Frame
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    rng = np.random.default_rng(3)
    src = AppSrc(spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
    sink = LlmServerSink(**{
        "id": srv_id, "model": "zoo:transformer_lm", "custom": MODEL_OPTS,
        "n-slots": N_SLOTS, "max-len": 64, "prompt-len": 16,
        "max-new-tokens": NEW, "pump": PUMP, "kv-layout": "paged",
        "block-size": 16, "stream": True})
    out_src, out_sink = LlmServerSrc(**{"id": srv_id, "stream": True}), AppSink()
    p = Pipeline().chain(src, sink)
    p.chain(out_src, out_sink)
    p.start()
    streams, done = {}, 0
    try:
        for i, n in enumerate(PROMPT_LENS):
            src.push(Frame((rng.integers(1, 211, (n,)).astype(np.int32),),
                           meta={"req": i}))
        src.end_of_stream()
        while done < len(PROMPT_LENS):
            f = out_sink.pop(timeout=120)
            assert f is not None, "serving pipeline drained early"
            if f.meta["done"]:
                done += 1
            else:
                streams.setdefault(f.meta["req"], []).append(
                    int(np.asarray(f.tensors[0])[0, 0]))
    finally:
        p.stop()
    return streams


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    """One traced run: (path of its ``.xplane.pb``, the token streams)."""
    import jax

    logdir = str(tmp_path_factory.mktemp("spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        streams = _serve("spans-traced")
    finally:
        jax.profiler.stop_trace()
    return glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1], streams


def _host_events(path):
    """(event, (plane, line index), line name) of every host event."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                yield ev, (plane.name, i), line.name


def _timed(ev, line, name):
    return {"name": name, "line": line, "start": ev.start_ns,
            "end": ev.start_ns + ev.duration_ns, "stats": dict(ev.stats)}


@pytest.fixture(scope="module")
def traced(trace_file):
    """(events of every ``nns.*`` name, XLA module names seen on the host's
    XLA lines, the token streams)."""
    path, streams = trace_file
    events, modules = [], set()
    for ev, line, line_name in _host_events(path):
        if ev.name.startswith("nns."):
            events.append(_timed(ev, line, ev.name))
        elif line_name.startswith("tf_XLAPjRtCpuClient"):
            modules.add(dict(ev.stats).get("hlo_module"))
    return events, modules, streams


@pytest.fixture(scope="module")
def ops(trace_file):
    """Every op a program ran on a host line (the CPU backend runs a small
    program on the thread that launched it), named by its module."""
    timed = (_timed(ev, line, None) for ev, line, _ in _host_events(trace_file[0]))
    return [dict(e, name=e["stats"]["hlo_module"]) for e in timed
            if "hlo_module" in e["stats"]]


# ``nns.moe.routing`` is written by the routed-expert families only and
# ``nns.state.update`` by the two with per-slot state; this run serves the
# dense block (tests/test_longcat.py, tests/test_kimi_linear.py and
# tests/test_granite_hybrid.py trace them)
@pytest.mark.parametrize(
    "name", sorted(set(trace.SPAN_CATALOG) - {"nns.moe.routing", "nns.state.update"}))
def test_every_cataloged_span_is_on_the_profilers_timeline(traced, name):
    events, _, _ = traced
    assert any(e["name"] == name for e in events), (
        f"{name} is cataloged but the traced serving run wrote no such event")


@pytest.mark.parametrize("family", ["kimi_linear", "granite_hybrid"])
def test_state_update_is_cataloged_for_both_families_with_per_slot_state(family):
    layer, emitter, attrs = trace.SPAN_CATALOG["nns.state.update"]
    assert f"models/{family}.py" in emitter and "slot_layers" in attrs
    assert layer == "KDA layers, SSM layers"
    assert f"models/{family}.py" in trace.SPAN_CATALOG["nns.moe.routing"][1]


def _inside(inner, outer):
    return (inner["line"] == outer["line"] and outer["start"] <= inner["start"]
            and inner["end"] <= outer["end"])


@pytest.mark.parametrize("phase", PUMP_PHASES)
def test_pump_phases_nest_in_pump_in_llm_pump_on_one_thread(traced, phase):
    events, _, _ = traced
    pumps = [e for e in events if e["name"] == "nns.pump"]
    llm_pumps = [e for e in events if e["name"] == "nns.llm.pump"]
    phases = [e for e in events if e["name"] == phase]
    assert phases and pumps and llm_pumps
    for e in phases:
        assert any(_inside(e, p) for p in pumps), (phase, e)
    for p in pumps:
        assert any(_inside(p, lp) for lp in llm_pumps), p


def test_harvest_and_emit_sit_where_the_catalog_says(traced):
    events, _, _ = traced
    llm_pumps = [e for e in events if e["name"] == "nns.llm.pump"]
    for h in (e for e in events if e["name"] == "nns.llm.harvest"):
        assert any(_inside(h, lp) for lp in llm_pumps)
    emits = [e for e in events if e["name"] == "nns.llm.emit"]
    assert emits and all(e["stats"]["frames"] >= 1 for e in emits)
    # a burst is what one pump left: it never overlaps a pump of its own thread
    for e in emits:
        for lp in llm_pumps:
            if lp["line"] == e["line"]:
                assert lp["end"] <= e["start"] or e["end"] <= lp["start"]
    # one span per burst, not one per frame
    frames = sum(len(t) for t in traced[2].values()) + len(PROMPT_LENS)
    assert len(emits) < frames


def test_a_requests_events_share_its_rid_in_time_order(traced):
    events, _, _ = traced
    rids = {e["stats"]["rid"] for e in events if e["name"] == "nns.req.submit"}
    assert len(rids) == len(PROMPT_LENS)
    for rid in rids:
        mine = [e for e in events if e["name"].startswith("nns.req.")
                and e["stats"].get("rid") == rid]
        mine.sort(key=lambda e: (e["start"], REQ_ORDER.index(e["name"])))
        assert tuple(e["name"] for e in mine) == REQ_ORDER, (rid, mine)


def test_attributes_round_trip(traced):
    events, _, _ = traced
    by = lambda n: [e for e in events if e["name"] == n]  # noqa: E731
    assert sorted(e["stats"]["prompt_tokens"] for e in by("nns.llm.submit")) \
        == sorted(PROMPT_LENS)
    assert all(e["stats"]["tokens"] == NEW and e["stats"]["tpot_ms"] > 0
               and e["stats"]["preemptions"] == 0 for e in by("nns.req.done"))
    assert all(e["stats"]["n_steps"] == PUMP for e in by("nns.pump"))
    assert all(0 <= e["stats"]["active"] <= N_SLOTS for e in by("nns.pump"))
    assert all(1 <= e["stats"]["active"] <= N_SLOTS for e in by("nns.pump.launch"))
    assert all(e["stats"]["queue_ms"] >= 0 for e in by("nns.req.prefill_start"))
    assert all(e["stats"]["ttft_ms"] > 0 for e in by("nns.req.first_token"))
    admitted = by("nns.llm.admitted")
    assert {e["stats"]["rid"] for e in admitted} \
        == {e["stats"]["rid"] for e in by("nns.req.submit")}
    # five requests on two slots: some submit waited, inside its span
    assert any(e["stats"]["retries"] > 0 and e["stats"]["slot_wait_ms"] > 0
               for e in admitted)
    for a in admitted:
        assert any(_inside(a, s) for s in by("nns.llm.submit"))


def test_admit_spans_count_what_they_spliced_in_one_launch(traced, ops):
    """``nns.pump.admit`` says how many requests it admitted; a span that
    admitted any holds the ops of ONE ``jit_nns_admit`` and no eager update
    (``jit_scatter``, ``jit_convert_element_type``), one that admitted none
    launches nothing."""
    events, _, _ = traced
    admits = [e for e in events if e["name"] == "nns.pump.admit"]
    assert all(0 <= e["stats"]["admitted"] <= N_SLOTS for e in admits)
    assert sum(e["stats"]["admitted"] for e in admits) == len(PROMPT_LENS)
    per_span = [[o["name"] for o in ops if _inside(o, a)] for a in admits]
    launched = [names for a, names in zip(admits, per_span)
                if a["stats"]["admitted"]]
    assert launched and all(
        names and set(names) == {"jit_nns_admit"} for names in launched)
    # the same ops each time: one launch a span, whatever it admitted
    assert len({tuple(sorted(names)) for names in launched}) == 1
    assert not any(names for a, names in zip(admits, per_span)
                   if not a["stats"]["admitted"])


def test_prefill_spans_count_their_buckets_and_activations(traced):
    """``nns.pump.prefill`` says how many bucket programs it launched and
    how many jobs it finalized (set as it closes): over the run, one
    activation a request and one bucket per ``prompt-len`` chunk of every
    prompt; a span never launches more buckets than max(1, its
    ``prefill_q``) unless nothing was decoding."""
    events, _, _ = traced
    spans = [e["stats"] for e in events if e["name"] == "nns.pump.prefill"]
    assert all({"prefill_q", "buckets", "programs", "prompts", "activated"}
               <= set(s) for s in spans)
    assert sum(s["activated"] for s in spans) == len(PROMPT_LENS)
    # a bucket of one block holds one prompt: every prompt's last program
    # completes it and no program completes two
    assert sum(s["prompts"] for s in spans) == len(PROMPT_LENS)
    assert all(s["programs"] == s["buckets"] >= s["prompts"] for s in spans)
    assert sum(s["buckets"] for s in spans) == sum(
        -(-n // 16) for n in PROMPT_LENS)
    assert all(s["activated"] <= max(1, s["buckets"]) for s in spans)


def test_programs_have_names_of_their_own_and_decode_keeps_impl(traced):
    _, modules, _ = traced
    assert "jit_impl" in modules, "the decode module the benchmark finds by name"
    assert {"jit_nns_prefill", "jit_nns_sample_first"} <= modules
    assert any(m.startswith("jit_nns_prefill_chunk") for m in modules if m)
    assert not [m for m in modules if m and "lambda" in m]


def test_token_stream_is_the_same_with_nobody_listening(traced):
    assert trace.get() is None
    assert _serve("spans-quiet") == traced[2]


def test_an_enabled_tracer_gets_the_same_spans_as_chrome_events():
    tr = trace.enable()
    try:
        with trace.span("nns.pump", n_steps=8, active=2, prefill_q=0):
            trace.instant("nns.req.submit", rid=7)
        evs = {e["name"]: e for e in tr.events()}
    finally:
        trace.disable()
    assert evs["nns.pump"]["ph"] == "X" and evs["nns.pump"]["dur"] >= 0
    assert evs["nns.pump"]["args"] == {"n_steps": 8, "active": 2, "prefill_q": 0}
    assert evs["nns.req.submit"]["ph"] == "i"
    assert evs["nns.req.submit"]["args"] == {"rid": 7}


# -- the benchmark's reduction of those spans (pure functions) ---------------

GAPS = [(0, 10), (20, 30), (50, 60)]


@pytest.mark.parametrize("cover,minus,want_ns", [
    ([(0, 10)], [], 10),                       # a whole gap
    ([(5, 25)], [], 10),                       # clipped to the gaps it touches
    ([(0, 100)], [], 30),                      # never more than the idle time
    ([(0, 6), (4, 10)], [], 10),               # two threads' spans count once
    ([(0, 10)], [(0, 4)], 6),                  # precedence: minus goes first
    ([(22, 28)], [(0, 100)], 0),               # wholly under the other class
    ([(12, 18)], [], 0),                       # a span while the device is busy
    ([], [], 0),
])
def test_overlap_seconds(cover, minus, want_ns):
    assert host_spans.overlap_seconds(GAPS, cover, minus) \
        == pytest.approx(want_ns * 1e-9)


def _ctx(events, busy, window=(0.0, 100.0)):
    ctx = {"trace": {"window_ns": window, "busy_intervals_ns": busy,
                     "window_s": (window[1] - window[0]) * 1e-9,
                     "busy_s": sum(b - a for a, b in busy) * 1e-9},
           "trace_names": {"decode": ["jit_impl"]}}
    host_spans._parsed[tuple(window)] = {"launches": [], "events": [
        {"name": n, "line": ln, "start_ns": s, "end_ns": e, "stats": {}}
        for n, ln, s, e in events]}
    return ctx


def test_three_shares_sum_to_the_idle_share():
    # busy 10-20 and 30-50: idle 0-10, 20-30, 50-100 = 70 of 100
    ctx = _ctx([("nns.llm.pump", "src", 0, 8), ("nns.pump", "src", 1, 7),
                ("nns.pump.wait", "src", 4, 7),
                ("nns.llm.emit", "src", 8, 10), ("nns.llm.emit", "src", 20, 26),
                ("nns.llm.pump", "sink", 24, 30),   # a back-pressure pump
                # a phase whose pump was open when the profiler stopped, and so
                # was never recorded, is still the pump's
                ("nns.pump.admit", "src", 95, 100),
                ("nns.llm.submit", "sink", 22, 60)],  # not a class of its own
               [(10, 20), (30, 50)])
    try:
        pump = host_spans.idle_overlap(ctx, host_spans.PUMP)
        emit = host_spans.idle_overlap(ctx, host_spans.EMIT, minus=host_spans.PUMP)
        assert pump == pytest.approx(19e-9)           # 0-8, 24-30, 95-100
        assert emit == pytest.approx(6e-9)            # 8-10 and 20-24
        idle = ctx["trace"]["window_s"] - ctx["trace"]["busy_s"]
        table = host_spans.idle_by_innermost(ctx)
        assert sum(table.values()) == pytest.approx(idle)
        assert table["unspanned"] == pytest.approx(idle - pump - emit)
        assert table["nns.pump.wait"] == pytest.approx(3e-9)
        assert table["nns.pump.admit"] == pytest.approx(5e-9)
        assert table["nns.llm.emit"] == pytest.approx(emit)
        assert "nns.llm.submit" not in table
    finally:
        host_spans._parsed.clear()


def test_no_program_span_reads_as_nothing_not_as_zero():
    ctx = _ctx([], [(10, 20)])
    try:
        assert host_spans.idle_overlap(ctx, host_spans.PUMP) is None
        assert host_spans.idle_by_innermost(ctx) is None
        assert host_spans.mean_stat(ctx, "nns.llm.admitted", "slot_wait_ms") \
            == (None, 0)
    finally:
        host_spans._parsed.clear()

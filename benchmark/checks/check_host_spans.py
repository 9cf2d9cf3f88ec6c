"""Check of benchmark/lib/host_spans.py and the readers that use it, on the
CPU: the rehearsal cell ``tiny.tiny-open-spans`` of ``REHEARSAL_SPANS.json``
(``REHEARSAL.json`` plus the program-span metrics and one busy open-loop cell)
runs with ``--trace 1`` and must print every new metric, the three idle shares
must sum to ``device_idle_pct``, and a trace directory whose marks are not the
window's is refused.

    JAX_PLATFORMS=cpu python3 benchmark/checks/check_host_spans.py

Not tier-1 (it runs a whole cell, about 20 s); the pure functions are also
covered by tests/test_trace_spans.py.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import host_spans  # noqa: E402

CELL = "tiny.tiny-open-spans"
NEW = ("idle_emit_pct.chat", "idle_pump_host_pct.chat", "idle_unspanned_pct.chat",
       "prefill_ms_per_pump.chat", "slot_wait_mean_ms",
       "prefill_queue_wait_mean_ms", "setup_weights_s", "setup_batcher_s",
       "setup_first_token_s")


def run_cell() -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--bench-file", os.path.join(HERE, "REHEARSAL_SPANS.json"), "--allow-cpu",
         "--workload", CELL, "--seed", "5", "--seconds", "5", "--trace", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_traced_rehearsal_prints_every_new_metric():
    line = run_cell()
    assert line["correct"] and line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    missing = [m for m in NEW if m not in got]
    assert not missing, f"the traced rehearsal's line lacks {missing}"
    parts = sum(got[f"idle_{k}_pct.chat"] for k in ("emit", "pump_host", "unspanned"))
    assert abs(parts - got["device_idle_pct.chat"]) < 0.1, (parts, got)
    assert got["idle_pump_host_pct.chat"] > 0
    assert got["setup_weights_s"] > 0 and got["setup_first_token_s"] > 0


def test_stale_trace_directory_is_refused():
    """The trace that run left is this window's only: asked for another
    window's spans, the parse refuses it and every reader finds nothing."""
    path = host_spans.newest_trace()
    assert path and os.sep + CELL + os.sep in path, path
    from benchmark.lib import xplane

    red = xplane.reduce_trace(path, allow_host=True)
    assert host_spans.parse(path, red["window_ns"])["events"]
    lo, hi = red["window_ns"]
    assert host_spans.parse(path, (lo + 1.0, hi)) is None
    ctx = {"trace": {**red, "window_ns": (lo, hi - 1.0)}}
    assert host_spans.idle_overlap(ctx, host_spans.PUMP) is None


if __name__ == "__main__":
    test_traced_rehearsal_prints_every_new_metric()
    test_stale_trace_directory_is_refused()
    print("check_host_spans: ok")

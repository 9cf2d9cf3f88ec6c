"""The comparison that decides ``correct`` for the Granite-4.0-H configuration,
at the CPU rehearsal size (``REHEARSAL_GRANITE_HYBRID.json``:
``tiny-granite-hybrid`` under ``tiny-decode``, float32 storage): the program
passes the cell's limits, and each control the configuration names (the
reference itself in bfloat16 activations, and with int8 weights besides, put in
the program's place on the same prompts and contexts) is judged not correct by
the same limits. A served token altered where a pump's rows are harvested fails
too.

    python3 -m pytest benchmark/checks/test_correct_granite_hybrid.py -q   (about a minute)
"""

import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve  # noqa: E402

CELL = "tiny-granite-hybrid.tiny-decode"


def _run(control=(), seed=7):
    with open(os.path.join(HERE, "REHEARSAL_GRANITE_HYBRID.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    args = types.SimpleNamespace(
        t0=time.perf_counter(), seed=seed, seconds=3.0, trace=0,
        out_dir=os.path.join(ROOT, "chiprun_out", "checks"),
        metrics_for=lambda name, traced: bench["end_to_end"],
        read_layer_metric=None)
    return serve.run(
        cell, load(cfg["file"]), load("benchmark", "traffic", cell["traffic"] + ".json"),
        load("benchmark", "cells", CELL + ".json"), args,
        require_chip=False, control=control, log=lambda m: None)


def test_program_passes_and_both_controls_fail():
    result = _run(control=["all"])
    assert result["correct"], result["checks"]
    assert set(result["controls"]) == {"bf16_activations", "int8_weights"}
    for name, verdict in result["controls"].items():
        assert not verdict["correct"], (name, verdict["checks"])


def test_altered_token_is_not_correct(monkeypatch):
    from nnstreamer_tpu.models.serving import ContinuousBatcher

    harvest = ContinuousBatcher._harvest_rows_locked

    def broken(self, active_np, rows):
        def altered(s):
            out = []
            for row in rows(s):
                row = list(row)
                if row and row[0] >= 0:
                    row[0] = (int(row[0]) + 1) % 211  # the tiny vocabulary
                out.append(row)
            return out

        return harvest(self, active_np, altered)

    monkeypatch.setattr(ContinuousBatcher, "_harvest_rows_locked", broken)
    result = _run()
    assert not result["correct"]
    assert [c["name"] for c in result["checks"] if not c["ok"]] == ["gap_mean"]


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))

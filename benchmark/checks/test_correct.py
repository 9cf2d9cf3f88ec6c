"""Two tests of the comparison that decides ``correct``, at a size a test
run can hold (the tiny rehearsal configuration, on whatever device jax has;
they skip the harness's look for a chip and drive the rest of a run).

1. Every control comes out as not correct: the plain reference computed in
   the precision below the one the configuration states (here bfloat16
   activations under a float32 configuration) and put in the program's place,
   on the same prompts and contexts, is judged by the cell's own limits
   (``check.judge``, in the driver) and fails, while the program passes.
2. With the timed path broken underneath - a token altered where it is
   produced (the batcher's harvest of a pump's rows) - ``correct`` is false.
   That is the one fault of the builder's list a serving cell can have: it
   has no training state, no batch mean and no exchange between chips.

    python3 -m pytest benchmark/checks/test_correct.py -q     (about a minute)
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

CELL = "tiny.tiny-closed"


def _run(control=(), seed=7):
    with open(os.path.join(HERE, "REHEARSAL.json")) as f:
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


def test_program_passes_and_control_fails():
    result = _run(control=["all"])
    assert result["correct"], result["checks"]
    assert result["controls"], "the configuration names no control"
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
    bad = [c["name"] for c in result["checks"] if not c["ok"]]
    assert bad == ["gap_mean"], result["checks"]


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))

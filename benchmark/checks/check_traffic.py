"""Check of the traffic generator's schedule: the same seed gives the same
due times, lengths and tokens; another seed gives the same lengths and gaps
in another order; lengths stay inside the mix's ranges and a server's
max-len; the open loop reports how late it ran.

    python3 benchmark/checks/check_traffic.py     (also collected by pytest)
"""

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import traffic  # noqa: E402


def _mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_schedule_repeats_and_rotates():
    mix = {**_mix("chat-steady"), "rate_rps": 1.3}
    a = traffic.open_schedule(mix, 2 ** 31 + 5, 51, 50304)
    b = traffic.open_schedule(mix, 2 ** 31 + 5, 51, 50304)
    c = traffic.open_schedule(mix, 77, 51, 50304)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() and x.out_len == y.out_len
               for x, y in zip(a, b))

    def window(s):
        return [r for r in s if 0 <= r.due < 51]

    wa, wc = window(a), window(c)
    assert len(wa) == len(wc) == int(1.3 * 51)      # the same work in every seed
    assert sorted(len(r.prompt) for r in wa) == sorted(len(r.prompt) for r in wc)
    assert sorted(r.out_len for r in wa) == sorted(r.out_len for r in wc)
    assert [len(r.prompt) for r in wa] != [len(r.prompt) for r in wc]
    ga, gc = np.diff([r.due for r in wa]), np.diff([r.due for r in wc])
    # the same gaps: each seed's window lacks only the one at its own seam
    assert len(set(np.round(ga, 9)) ^ set(np.round(gc, 9))) <= 2
    assert a[0].due < 0 and -4.0 <= a[0].due       # the ramp comes first
    assert [r.due for r in a] == sorted(r.due for r in a)
    for r in a:
        assert 32 <= len(r.prompt) <= 512 and 32 <= r.out_len <= 128
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50304


def test_closed_loop_repeats():
    mix = _mix("batch-saturated")
    g1, g2 = (traffic.ClosedLoop(mix, 9, 16, 32000) for _ in range(2))
    assert g1.clients == 32
    for _ in range(100):
        x, y = g1.next(), g2.next()
        assert (x.prompt == y.prompt).all() and x.out_len == y.out_len
        assert 64 <= len(x.prompt) <= 512 and 64 <= x.out_len <= 256
        assert len(x.prompt) + x.out_len <= 1024


def test_open_loop_reports_lateness():
    """A push that blocks makes the next requests late, and the load thread
    says by how much: timed from when each was due."""
    from benchmark.drivers.serve import Load, Recorder

    class SlowSrc:
        def push(self, frame):
            time.sleep(0.05)

    sched = [traffic.Request(i, 0.01 * i, np.zeros(4, np.int32), 4)
             for i in range(5)]
    load = Load(SlowSrc(), Recorder(), lambda r: r)
    load.run_open(sched, time.perf_counter())
    load.thread.join(5.0)
    assert len(load.late_ms) == 5 and load.late_ms[-1] > 100
    assert min(load.block_ms) >= 45


if __name__ == "__main__":
    test_open_schedule_repeats_and_rotates()
    test_closed_loop_repeats()
    test_open_loop_reports_lateness()
    print("check_traffic: ok")

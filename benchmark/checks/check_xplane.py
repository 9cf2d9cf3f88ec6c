"""Check of benchmark/lib/xplane.py against the small trace recorded on the
chip and kept beside this file (``recorded/tiny.xplane.pb.gz``: the tiny
rehearsal configuration on a TPU v5 lite, 0.27 s traced). The expected
numbers were read independently, from the profiler's own chrome trace of the
same session.

    python3 benchmark/checks/check_xplane.py      (also collected by pytest)
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import xplane  # noqa: E402


def _unpacked() -> str:
    out_dir = os.path.join(ROOT, "chiprun_out", "checks")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "tiny.xplane.pb")
    with gzip.open(os.path.join(HERE, "recorded", "tiny.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            dst.write(src.read())
    return path


def test_union_and_gaps():
    assert xplane.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.union_seconds([]) == 0
    assert xplane.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xplane.gaps([(0, 6)], 0, 6) == []
    assert xplane.op_name("%while.50 = (s32[]{:T(128)}) while(...)") == "while.50"
    assert xplane.module_kind("jit_impl(504052902497080924)") == "jit_impl"


def test_recorded_trace():
    with open(os.path.join(HERE, "recorded", "tiny.expected.json")) as f:
        want = json.load(f)
    got = xplane.reduce_trace(_unpacked())
    assert got["marks_found"] and got["devices"] == 1
    assert abs(got["window_s"] - want["window_s"]) < 1e-6
    assert abs(got["busy_s"] - want["busy_s"]) < 1e-6
    for name, (secs, launches) in want["modules"].items():
        g = got["modules"][name]
        assert abs(g[0] - secs) < 1e-7 and g[1] == launches, (name, g)
    decode = [v for k, v in got["modules"].items()
              if xplane.module_kind(k) == "jit_impl"]
    assert sum(v[1] for v in decode) == want["decode_launches"]
    lo, hi = got["window_ns"]
    idle = sum(b - a for a, b in xplane.gaps(got["busy_intervals_ns"], lo, hi))
    assert abs(idle * 1e-9 - (got["window_s"] - got["busy_s"])) < 1e-6


if __name__ == "__main__":
    test_union_and_gaps()
    test_recorded_trace()
    print("check_xplane: ok")

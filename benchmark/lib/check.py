"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of the
requests the window finished (drawn from the seed, the longest in it) is run
through the plain reference the configuration names (its ``reference``
block: ``{"module": "transformer", "precision": "bf16_operands",
"controls": [...]}``; the module is ``benchmark/reference/<module>.py``):
one forward over each prompt with its served tokens, in the precision the
configuration states. For every served token the reference gives the gap by
which that token's logit lies below the reference's best. Greedy decoding in
the stated precision keeps every gap within rounding; a token altered where
it is produced, a wrong cache read or a lower precision does not.

A control is the reference itself in a lower precision, put in the program's
place: at each position of the same prompts and served tokens, the token it
puts first is read against the stated-precision reference as a served token
would be, and judged by the same limits.

Numbers produced (which of them are held to a limit is the cell's file's to
say, ``benchmark/cells/<cell>.json`` "limits"; PERF.md section 6 has the
readings behind each):
  gap_mean       mean gap over the sampled served tokens (logit units)
  gap_max        widest gap over them
  mismatch_share share of them that are not the reference's own best token
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional

import numpy as np

SAMPLE = 64  # requests compared: a few thousand served tokens (see draw_sample)
_REFERENCES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reference")


def load_reference(name: str):
    """``benchmark/reference/<name>.py``, found by the name the
    configuration's file gives, as drivers and readers are."""
    path = os.path.join(_REFERENCES, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no reference benchmark/reference/{name}.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def draw_sample(finished: List[dict], seed: int) -> List[dict]:
    """Up to ``SAMPLE`` finished requests drawn from the seed, the longest
    (prompt + served tokens) always among them. Many, not a few hundred
    tokens' worth: a sound program and a lower precision differ in a few
    served tokens of a hundred, so the mean gap is made of few events and
    steadies only with the number of tokens compared (PERF.md section 6)."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(len(r["prompt"]) + len(r["tokens"])),
                                            r["rid"]))
    longest, rest = order[0], order[1:]
    rng = np.random.default_rng(seed)
    picks = [rest[i] for i in rng.permutation(len(rest))[:SAMPLE - 1]]
    return [longest] + picks


def gaps(refspec: dict, sizes: dict, wseed: int, sample: List[dict], pad_to: int,
         control: Optional[str] = None) -> Dict[str, float]:
    """Run the reference (``refspec``: the configuration's ``reference``
    block) over the sample. With ``control``, a lower precision of the same
    reference, the served tokens are replaced position by position by the
    token that precision puts first, on the same prompts and contexts."""
    ref = load_reference(refspec["module"])
    toks = np.zeros((SAMPLE, pad_to), np.int32)
    served = np.zeros((SAMPLE, pad_to), bool)  # positions that predict a served token
    for i, r in enumerate(sample):
        p, n = len(r["prompt"]), len(r["tokens"])
        toks[i, : p + n] = np.concatenate([r["prompt"], r["tokens"]])
        served[i, p - 1: p - 1 + n] = True
    want = np.roll(toks, -1, axis=1)  # the token served after each position
    if control:
        want = np.asarray(ref.score(sizes, wseed, toks, want, control)[1])
    best, _, at_want = ref.score(sizes, wseed, toks, want, refspec["precision"])
    g = (np.asarray(best) - np.asarray(at_want))[served]
    if g.size == 0:
        return {"gap_max": float("inf"), "gap_mean": float("inf"),
                "mismatch_share": 1.0, "tokens_compared": 0}
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "mismatch_share": float((g > 0).mean()),
            "tokens_compared": int(g.size)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each number that has a limit, beside it."""
    return [{"name": k, "value": numbers[k], "limit": lim,
             "ok": bool(numbers[k] <= lim)} for k, lim in limits.items()]

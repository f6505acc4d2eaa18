"""What decides ``correct``: the served tokens against the reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the
longest, is run through the float32 reference of the configuration's
family (``gaps`` of ``bench/reference/<module>.py``): each prompt with
its served tokens, at once.  For each served token the gap is how far
its reference logit lies below the reference's best at its position.
Greedy serving puts first the token its own bf16 logits rank first, so a
sound run reads gaps of rounding size; a step that loses state, a lane
that reads another's cache or an altered token reads gaps the size of
the logits' own spread.  The numbers are the widest gap
(``logit_gap``), the mean gap (``logit_gap_mean``) and the share of
tokens that are not the reference's best; the configuration file's
``check.limits`` names those a cell compares, with their limits.
"""

from __future__ import annotations

import math

import numpy as np

from .drive import ReqRec


def sample(reqs: list[ReqRec], seed: int, tokens: int) -> list[ReqRec]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``tokens`` served tokens are in the sample."""
    done = [r for r in reqs if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.out_len, -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out = [longest]
    n = longest.out_len
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += rest[i].out_len
    return out


def _stats(gaps: np.ndarray, prefix: str = "") -> dict:
    return {f"{prefix}logit_gap": float(gaps.max()),
            f"{prefix}logit_gap_mean": float(gaps.mean()),
            f"{prefix}not_best_share": float((gaps > 0).mean())}


def compare(family, conf: dict, weights,
            engine_outputs: dict[int, list[int]], picked: list[ReqRec],
            control: bool = False) -> dict:
    """Over the served tokens of the picked requests, by the family
    module's reference: the widest gap, the mean gap and the share of
    tokens that are not the reference's best; with ``control``, the same
    of the control's first tokens."""
    served, ctl = [np.zeros(0)], [np.zeros(0)]
    for r in picked:
        s, c = family.gaps(conf, weights, r.prompt, engine_outputs[r.rid],
                           control=control)
        served.append(s)
        if control:
            ctl.append(c)
    served = np.concatenate(served)
    out = {"tokens_compared": int(served.size),
           "requests_compared": len(picked)}
    if served.size:
        out.update(_stats(served))
        if control:
            out.update(_stats(np.concatenate(ctl), "control_"))
    return out


def control_numbers(numbers: dict) -> dict:
    """The control's numbers under the names the program's go by."""
    out = {k[len("control_"):]: v for k, v in numbers.items()
           if k.startswith("control_")}
    out["tokens_compared"] = numbers["tokens_compared"]
    return out


def verdict(conf: dict, numbers: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit: the
    configuration file's ``check.limits`` name the numbers compared."""
    checks = {name: {"value": numbers.get(name, math.inf), "max": limit}
              for name, limit in conf["check"]["limits"].items()}
    checks["tokens_compared"] = {"value": numbers["tokens_compared"], "min": 1}
    ok = (all(c["value"] <= c["max"] for c in checks.values() if "max" in c)
          and numbers["tokens_compared"] >= 1)
    return ok, checks

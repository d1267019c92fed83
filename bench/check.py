"""What decides ``correct`` for a serving cell.

Every request due in the window is compared once the window has closed.
The numbers, each against the limit the configuration gives it
(``limits``; a number without one is printed, not compared):

* ``unanswered``: requests whose future never resolved to tokens, or whose
  decision row the timed path never returned. Limit 0.
* ``token_mismatches``: answers that differ from the reference policy run
  on the (a, b) the timed path returned for that request, or from the
  tokens its own decision row held. Limit 0.
* ``runtime_gap``: the widest relative gap between the runtime a decision
  row predicts and ``b * tokens ** a`` in float64 on that row's own (a, b,
  tokens): the policy's arithmetic, which the configuration states in
  float64.
* ``ab_gap``: the widest relative gap between a served a or b and the
  reference forward's for the request's plan.
* ``ab_gap_median``: the median of that gap over the requests.

Each control puts the reference in the program's place with one of the
configuration's precisions one step down (``control``): the forward's
(``forward``) or the policy's (``policy``).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, Tuple

import numpy as np

from bench import reference


@dataclasses.dataclass
class Served:
    answers: np.ndarray      # tokens the future resolved to, -1 if none
    a: np.ndarray            # (a, b) the timed path returned, NaN if none
    b: np.ndarray
    tokens: np.ndarray       # tokens in the same decision row
    runtime: np.ndarray      # runtime the same row predicts


def reference_for(config: Dict, params, sched) -> Tuple[np.ndarray,
                                                         np.ndarray]:
    """Per-plan reference (a, b) at the configuration's precision."""
    z = reference.template_z(config, params, sched.jobs)
    return reference.decode(z, config["normalization"]["pcc_scaler"])


def controls(config: Dict) -> Dict[str, Dict[str, str]]:
    """``{name: keyword arguments of control_served}``: one control for
    each precision the configuration lowers."""
    return {part: {part: dtype} for part, dtype in config["control"].items()}


def control_served(config: Dict, params, sched, forward: str = "float32",
                   policy: str = "float64") -> Served:
    """The reference in the program's place: the forward's operands,
    activations and sums in ``forward``, the decode and the policy in
    ``policy``."""
    pdt = np.dtype(policy).type
    z = reference.template_z(config, params, sched.jobs, forward)
    a, b = reference.decode(z, config["normalization"]["pcc_scaler"], pdt)
    a, b = a[sched.pick], b[sched.pick]
    toks = reference.policy_tokens(a, b, sched.observed, config["policy"],
                                   pdt)
    rt = b * toks.astype(pdt) ** a
    return Served(answers=toks, a=a.astype(np.float64),
                  b=b.astype(np.float64), tokens=toks,
                  runtime=rt.astype(np.float64))


def check(config: Dict, params, sched, served: Served) -> Dict[str, Dict]:
    """Compare ``served`` with the reference."""
    return compare(config, sched, served,
                   reference_for(config, params, sched))


def relative_gap(x, ref) -> np.ndarray:
    return np.abs(x - ref) / np.abs(ref)


def compare(config: Dict, sched, served: Served, want) -> Dict[str, Dict]:
    """``{name: {"value", "limit"}}`` for each number the configuration
    limits; every number is printed."""
    a_ref, b_ref = want
    seen = (served.answers >= 0) & np.isfinite(served.a) \
        & np.isfinite(served.b) & np.isfinite(served.runtime)
    a, b = served.a[seen], served.b[seen]
    toks = reference.policy_tokens(a, b, sched.observed[seen],
                                   config["policy"])
    bad = (toks != served.answers[seen]) \
        | (served.tokens[seen] != served.answers[seen])
    for i in np.flatnonzero(bad)[:3]:
        print(f"token mismatch: a {a[i]!r} b {b[i]!r} observed "
              f"{sched.observed[seen][i]} served {served.answers[seen][i]} "
              f"row {served.tokens[seen][i]} reference {toks[i]}",
              file=sys.stderr, flush=True)
    row = served.tokens[seen].astype(np.float64)
    rt_gap = relative_gap(served.runtime[seen], b * row ** a)
    pick = sched.pick[seen]
    ab = np.maximum(relative_gap(a, a_ref[pick]), relative_gap(b, b_ref[pick]))
    numbers = {
        "unanswered": int(len(sched) - seen.sum()),
        "token_mismatches": int(np.sum(bad)),
        "runtime_gap": float(rt_gap.max()) if rt_gap.size else 0.0,
        "ab_gap": float(ab.max()) if ab.size else 0.0,
        "ab_gap_median": float(np.median(ab)) if ab.size else 0.0,
    }
    print("compared: " + json.dumps(numbers), file=sys.stderr, flush=True)
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in config["limits"].items()}


def is_correct(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: Dict[str, Dict]) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)

"""Whether the timed submits produced the right answers.

The configuration's plain reference (``reference/<name>.py``) computes
the rows that the window kept from every submit, at the cell's own
sizes, from the seed of that submit's input, once the window has
closed; its ``compare`` gives the numbers
that the configuration's ``check.limits`` bound.  Two more numbers hold
for every cell, each with the limit 0: ``wg_not_once``, the work-groups
that a submit's packets did not cover exactly once (the scheduler's
coverage), and ``failed``, the submits that raised.

The control is the same reference computed in the nearest precision
below the configuration's (bfloat16 for float32), put in the program's
place: ``control_numbers`` compares it with the reference at the same
rows.  It has to fail a limit.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

from bench.registry import load_module

CONTROL_DTYPE = {"float32": "bfloat16"}


def coverage_faults(submits: Sequence) -> int:
    """Dim-0 units of the run regions not covered exactly once."""
    bad = 0
    for s in submits:
        counts = np.zeros(s.n_rows // s.unit_rows, np.int64)
        for p in s.result.packets:
            counts[p.offset:p.offset + p.size] += 1
        bad += int(np.count_nonzero(counts != 1))
    return bad


def _by_input(submits: Sequence) -> Dict[int, List]:
    """The submits grouped by the seed of their program's input."""
    groups: Dict[int, List] = {}
    for s in submits:
        groups.setdefault(s.input_seed, []).append(s)
    return groups


def _expected(ref, submits: Sequence) -> Iterator[np.ndarray]:
    """The reference's rows for each submit's sample, in submit order
    (the union of the sampled rows is computed once)."""
    rows = np.unique(np.concatenate([s.sample_rows for s in submits]))
    table = ref.rows(rows)
    for s in submits:
        yield (table[np.searchsorted(rows, s.sample_rows)]
               [:, s.col0:s.col0 + s.n_cols])


def _pairs(config: Dict, submits: Sequence,
           got_dtype: str = None) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """(got, want) per submit, one input's reference at a time: ``got``
    is the submit's sample, or with ``got_dtype`` the reference computed
    in that precision at the same rows."""
    mod = load_module("reference", config["reference"])
    for input_seed, group in _by_input(submits).items():
        want = _expected(mod.Reference(config, input_seed, config["dtype"]),
                         group)
        if got_dtype is None:
            got = (s.sample for s in group)
        else:
            got = _expected(mod.Reference(config, input_seed, got_dtype),
                            group)
        yield from zip(got, want)


def numbers(config: Dict, submits: Sequence,
            errors: Sequence[str]) -> Dict[str, float]:
    """The compared numbers of one run (every one of them is bounded)."""
    out: Dict[str, float] = {"failed": float(len(errors)),
                             "wg_not_once": float(coverage_faults(submits))}
    if not submits:
        out["submits_missing"] = 1.0
        return out
    mod = load_module("reference", config["reference"])
    out.update(mod.compare(_pairs(config, submits)))
    return out


def control_numbers(config: Dict, submits: Sequence) -> Dict[str, float]:
    """The reference in the next lower precision, compared at the rows
    that ``submits`` kept, in the program's place."""
    mod = load_module("reference", config["reference"])
    low = CONTROL_DTYPE[config["dtype"]]
    return mod.compare(_pairs(config, submits, low))


def limits(config: Dict) -> Dict[str, float]:
    return {"failed": 0.0, "wg_not_once": 0.0, "submits_missing": 0.0,
            **{k: float(v) for k, v in config["check"]["limits"].items()}}


def verdict(nums: Dict[str, float],
            lims: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit; a number without a limit is an error."""
    table = {k: {"value": v, "limit": lims[k]} for k, v in nums.items()}
    ok = all(np.isfinite(v) and v <= lims[k] for k, v in nums.items())
    return ok, table

"""The comparisons that decide `correct`.  Pure arithmetic on numbers the
runners hand in; every number compared is printed beside its limit."""
import math
import statistics

from common import log


def judge(numbers, limits, what):
    """``numbers`` and ``limits`` keyed alike; a number over its limit, a
    missing limit or a number that is not finite makes the run not correct."""
    ok = True
    for key, value in numbers.items():
        limit = limits.get(key)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        log(f"check {what}: {key} = {value!r} (limit {limit!r}) "
            f"{'ok' if good else 'NOT OK'}")
        ok = ok and good
    return ok


def judge_stated(read, stated, what):
    """What the process that holds the chip read of the program it built
    (``{"param_dtype": ..., ...}``) against what the configuration states
    under the same keys.  A precision that cannot be told apart by its
    numbers is held here: storage in another type than the one stated makes
    the run not correct, whatever its logits read."""
    ok = True
    for key, value in read.items():
        if key not in stated:
            continue
        good = value == stated[key]
        log(f"check {what}: {key} = {value!r} (stated {stated[key]!r}) "
            f"{'ok' if good else 'NOT OK'}")
        ok = ok and good
    return ok


def serve_numbers(all_gaps):
    """The two numbers a served model is held to: the widest gap (against a
    token altered where it is produced) and the mean gap, which grows with the
    square of the logits' error and is what a lower precision fails."""
    import numpy as np
    g = np.concatenate([np.asarray(x, np.float64) for x in all_gaps])
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "tokens": int(g.size),
            "flipped": int((g > 0).sum())}


def leaf_gaps(program, reference):
    """For every leaf, the gap between the program's norm and the
    reference's, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    med = statistics.median(reference.values())
    return {name: abs(program[name] - ref) / max(ref, med)
            for name, ref in reference.items()}


def train_numbers(program, reference):
    """``program`` and ``reference``: ``{"losses": [...], "grad_norms": {...},
    "delta_norms": {...}}`` over the same steps.

    * ``loss_first``: at the seed's weights, before any update;
      ``loss_later``: after Adam's first steps, which at seeded weights and no
      warm-up amplify rounding into a different spike (PERF.md, PR 23);
    * ``grad_norm``: the worst leaf of the first gradient; ``grad_rms``: the
      root mean square of the gaps of the leaves that the reference names
      under ``"rms_leaves"``, each measured against its own norm or the
      median of those leaves.  It does not swing with the one worst leaf nor
      with the size of the seed's gradient, and is what a lower precision
      fails;
    * ``delta_total``: the norm of the parameters' change over all leaves that
      have a gradient.  By the worst leaf it reads up to 0.5 in sound runs
      (Adam moves an element by the full rate whatever the size of its
      gradient, so the sign of rounding noise becomes a full-size move), which
      would hide the fault it is there to catch: a step that returns its state
      unchanged reads 1.
    """
    gaps = [abs(a - b) for a, b in zip(program["losses"],
                                       reference["losses"])]
    grad = leaf_gaps(program["grad_norms"], reference["grad_norms"])
    worst = max(grad, key=grad.get)
    sel = reference["rms_leaves"]
    sel_gaps = leaf_gaps({k: program["grad_norms"][k] for k in sel},
                         {k: reference["grad_norms"][k] for k in sel})
    # a leaf whose gradient is zero by the mathematics (a key bias: the
    # softmax does not see a constant added to a row) moves by noise alone
    floor = 1e-3 * statistics.median(reference["grad_norms"].values())
    live = [k for k, v in reference["grad_norms"].items() if v >= floor]

    def total(norms):
        return math.sqrt(sum(norms[k] ** 2 for k in live))
    ref_total = total(reference["delta_norms"])
    delta = leaf_gaps({k: program["delta_norms"][k] for k in live},
                      {k: reference["delta_norms"][k] for k in live})
    log(f"check train: worst gradient leaf {worst}; worst change leaf "
        f"{max(delta, key=delta.get)} at {max(delta.values()):.4f}; "
        f"{len(live)} of {len(grad)} leaves have a gradient")
    return {"loss_first": gaps[0], "loss_later": max(gaps[1:] or [0.0]),
            "grad_norm": grad[worst],
            "grad_rms": math.sqrt(sum(g * g for g in sel_gaps.values())
                                  / len(sel_gaps)),
            "delta_total": abs(total(program["delta_norms"]) - ref_total)
            / ref_total}

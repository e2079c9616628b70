"""The comparison that decides ``correct``.

Two layers are held to it:

* the model step: logits that the window's quanta returned, for a sample
  of quanta drawn from the seed (with the first quantum of every served
  (model, exit, batch) in it, so the deepest exits served are always
  there), against the plain float32 reference on the same images:
  ``logit_err``, the largest absolute logit difference, each row
  normalised by the spread (standard deviation over classes) of the
  reference's logits for that request. (The gap by which the reference's
  logit for the program's top class lies below its best does not separate
  the served precision from the control: see PERF.md.);
* the engine's accounting: every request due in the window is completed,
  dropped or stranded exactly once (``accounting_errors``, limit 0).
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


def logit_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want|`` over rows ``[rows, classes]``, each row
    over the standard deviation of its reference logits."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return float((np.abs(got - want).max(axis=1) / want.std(axis=1)).max())


def compare(served: Iterable[tuple], refs: Dict[int, Dict[int, list]]
            ) -> Dict[str, float]:
    """``served``: (model, exit, batch, logits [b, classes]);
    ``refs[m][b][e]``: reference logits for the same images. No quantum
    to compare reads as infinitely wrong."""
    errs = [logit_err(out, refs[m][b][e]) for m, e, b, out in served]
    return {"logit_err": max(errs, default=float("inf"))}


def accounting_errors(n: int, completed_ids: Iterable[int],
                      queued_ids: Iterable[int], unsubmitted: int,
                      dropped: int) -> int:
    """Requests ``0..n-1`` must each be completed, queued at the end
    (stranded), never ingested (the trace's last ``unsubmitted``) or among
    the ``dropped`` exactly once: duplicates, unknown ids and a missing
    count that differs from ``dropped`` each count as errors."""
    ids = np.concatenate([
        np.fromiter(completed_ids, dtype=np.int64),
        np.fromiter(queued_ids, dtype=np.int64),
        np.arange(n - unsubmitted, n, dtype=np.int64)])
    known = (ids >= 0) & (ids < n)
    counts = np.bincount(ids[known], minlength=n)
    duplicates = int(np.clip(counts - 1, 0, None).sum())
    missing = int((counts == 0).sum())
    return duplicates + int((~known).sum()) + abs(missing - int(dropped))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit; a missing limit fails."""
    return all(limits.get(k) is not None and np.isfinite(v)
               and v <= limits[k] for k, v in numbers.items())

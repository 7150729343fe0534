"""The comparison that decides ``correct``.

Two numbers are compared, each with its limit in the configuration file.

The program's logits at a sample of (decode step, batch row) pairs of the
window are compared with the plain reference's at the same pairs.  The
program's token at a pair is its own greedy choice, the argmax of its
logits over the real vocabulary; the number compared is the widest gap by
which the reference's logit of that token lies below the reference's best
logit.  A non-finite program logit makes the gap infinite.

The second, ``logit_err``, is dense where the first is not: at each pair,
the root-mean-square difference between the program's and the reference's
logits over the vocabulary, in units of the reference logits' standard
deviation there; the largest over the pairs.  A greedy token can survive a
coarse computation where the top logits stand apart, so the gap alone can
read 0 for a lower precision; the error over the whole vocabulary cannot.
"""
from __future__ import annotations

import numpy as np


def gaps(program: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """program (n, >= V), ref (n, V) -> (n,) reference-logit gaps of the
    program's greedy tokens."""
    v = ref.shape[1]
    prog = program[:, :v]
    bad = ~np.all(np.isfinite(prog), axis=1)
    served = np.argmax(np.where(np.isfinite(prog), prog, -np.inf), axis=1)
    g = ref.max(axis=1) - ref[np.arange(ref.shape[0]), served]
    return np.where(bad, np.inf, g)


def rel_err(program: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """program (n, >= V), ref (n, V) -> (n,) RMS logit difference over
    the vocabulary / the reference logits' standard deviation."""
    prog = program[:, :ref.shape[1]].astype(np.float64)
    diff = np.sqrt(np.mean((prog - ref) ** 2, axis=1))
    err = diff / ref.std(axis=1)
    return np.where(np.isfinite(err), err, np.inf)

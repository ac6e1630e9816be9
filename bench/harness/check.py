"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the reference: each prompt with its served tokens, teacher-forced.
At every served token the reference's best logit and the logit of the
token that was served are read; their difference is the token's gap (0
where the program served the reference's own choice; greedy tokens only,
so the gap is what a served token gave up).  Two numbers of the gaps are
compared, each with a limit of its own (``stats``): the widest gap, which
catches a token gone badly wrong, and the mean gap, which catches a
model computed coarsely throughout.

The controls put the reference in the program's place at a lower
precision (``reference.py``'s ``int8`` and ``fp8``): at each of the same
positions they take the token that precision ranks first and read that
token's gap under the float32 reference.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from harness import gen, reference


def sample(reqs: Sequence, seed: int, tokens: int, most: int) -> List:
    """Finished requests with every token they asked for: the longest,
    then others in seeded order, until ``tokens`` served tokens or
    ``most`` requests are in the sample."""
    done = [r for r in reqs if r.finish is not None
            and len(r.tokens) == r.max_tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.uid)
    first = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not first]
    order = gen.rng_for(seed, 3).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _inputs(reqs: Sequence):
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in reqs]
    reads = [np.arange(r.plen - 1, r.plen - 1 + len(r.tokens)) for r in reqs]
    return seqs, reads


def served_gaps(c: dict, seed: int, reqs: Sequence) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    seqs, reads = _inputs(reqs)
    ref = reference.logits_at(c, seed, seqs, reads)
    return np.concatenate([
        lg.max(-1) - lg[np.arange(len(r.tokens)), np.asarray(r.tokens)]
        for lg, r in zip(ref, reqs)])


def stats(gaps: np.ndarray) -> dict:
    """The numbers of a sample's gaps that ``correct`` compares."""
    return {"logit_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}


def control_gaps(c: dict, seed: int, reqs: Sequence,
                 precision: str) -> np.ndarray:
    """Per position of the same tokens: the gap, under the float32
    reference, of the token the lower precision ranks first."""
    seqs, reads = _inputs(reqs)
    ref = reference.logits_at(c, seed, seqs, reads)
    low = reference.logits_at(c, seed, seqs, reads, precision=precision)
    return np.concatenate([
        r.max(-1) - r[np.arange(len(r)), lo.argmax(-1)]
        for r, lo in zip(ref, low)])

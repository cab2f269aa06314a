"""The comparison that decides ``correct``.

A served token's gap is how far the reference's logit for that token
lies below the reference's best logit at the same position: 0 where the
program chose what the reference would, small where the two nearly tie
and rounding decided, large where the program computed something else.
The number compared is the widest gap over every token of the sample.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def pick_sample(finished: Dict[int, Tuple[np.ndarray, List[int]]],
                seed: int, tokens: int, requests: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, then
    others drawn from ``seed`` until at least ``tokens`` served tokens
    and ``requests`` requests are in (or every finished one is)."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][1]), -r))
    rng = np.random.default_rng([seed, 7])
    order = [longest] + [r for r in rng.permutation(rids) if r != longest]
    out, n = [], 0
    for r in order:
        if n >= tokens and len(out) >= requests:
            break
        out.append(int(r))
        n += len(finished[r][1])
    return out


def teacher_forced(prompt: np.ndarray, served: Sequence[int]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(sequence, rows): the prompt followed by every served token but
    the last, and the positions at which each served token was chosen."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, rows


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """Per position, reference best minus reference logit of ``chosen``."""
    chosen = np.asarray(chosen, np.int64)
    best = ref_logits.max(-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]

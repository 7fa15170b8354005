"""Periodic summary refresh — the paper's motivating scenario (§2.1).

Client data is non-stationary, so summaries must be recomputed as data
drifts.  The registry tracks per-client summaries plus a *cheap* drift
signal: the P(y) label distribution (O(C), essentially free per the paper's
Table 2).  A client's expensive encoder summary is refreshed when

  * it has never been computed,
  * its age exceeds ``max_age_rounds``, or
  * the cheap P(y) drifted beyond ``kl_threshold`` (symmetric KL)

— which is how the cheap summary and the paper's efficient summary compose
into an adaptive refresh policy instead of a fixed period.

This module holds the policy and the exact host math: ``sym_kl`` is the
per-client predicate's drift, ``batch_sym_kl`` the same math row-wise.  The
registry that applies them to the fleet is ``repro.stream.SummaryRegistry``
(DESIGN.md §5), whose device scan re-checks borderline rows with
``batch_sym_kl``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def sym_kl(p: np.ndarray, q: np.ndarray, eps: float = 1e-9) -> float:
    p = p + eps
    q = q + eps
    p = p / p.sum()
    q = q / q.sum()
    return float(0.5 * (np.sum(p * np.log(p / q)) + np.sum(q * np.log(q / p))))


def batch_sym_kl(p: np.ndarray, q: np.ndarray, eps: float = 1e-9) -> np.ndarray:
    """Row-wise symmetric KL: ``[N, C] x [N, C] -> [N]``.

    Elementwise math mirrors ``sym_kl`` exactly (same eps, same dtype
    promotion, same reduction axis) so a batched scan reproduces the
    per-client loop's decisions bit-for-bit.
    """
    p = np.asarray(p) + eps
    q = np.asarray(q) + eps
    p = p / p.sum(axis=-1, keepdims=True)
    q = q / q.sum(axis=-1, keepdims=True)
    return 0.5 * (np.sum(p * np.log(p / q), axis=-1)
                  + np.sum(q * np.log(q / p), axis=-1))


@dataclasses.dataclass
class RefreshPolicy:
    max_age_rounds: int = 20
    kl_threshold: float = 0.05

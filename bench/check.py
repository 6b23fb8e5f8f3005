"""How ``correct`` is decided: answers against the plain reference.

Each checked answer is a top-k list ``(v_i, est_i)`` for a source ``u``,
computed on the graph version the answer reports; ``t`` is exact SimRank
on that version (``configs/simrank_power.py``), with ``t[u]`` left out.
Errors are measured in two units:

* against the configuration's guarantee ``eps_a``: every answer
  certifies a bound within it (``bound_over_eps <= 1``), every score lies
  within ``eps_a`` of the truth (``err_over_bound <= 1``) and, as follows
  from that, the i-th returned node lies within ``2 eps_a`` of the i-th
  best (``deficit_over_bound <= 2``);
* against the sampling noise the stated walk budget allows: an estimate
  is a mean of ``walks`` per-walk values in ``[0, 1]`` whose mean is
  ``t``, so its variance is at most ``t / walks``, and
  ``z = (est - t) / sqrt((t + T_FLOOR) / walks)``.  Reported are the
  largest ``|z|`` over all returned pairs (``z_max``), the root mean
  square of ``z`` over them (``z_rms``) and the largest rank deficit
  ``t*_i - t(v_i)`` in the same unit (``rank_z``).  A largest value swings
  from run to run by its nature; ``z_rms`` is steady, and a walk budget
  cut ``f``-fold multiplies it by about ``sqrt(f)``.  The configuration's
  ``limits`` name the numbers compared; PERF.md gives the readings each
  limit was set from.
"""
from __future__ import annotations

import numpy as np

T_FLOOR = 1e-8  # keeps z finite where the truth is (numerically) zero


def well_formed(req: dict, body: dict, *, n: int, k: int) -> bool:
    """A 200 answer says what a top-k answer must say: ``k`` distinct
    in-range nodes without the source, scores finite and non-increasing,
    a graph version and an error bound."""
    try:
        nodes, scores = body["topk_nodes"], body["topk_scores"]
        if body["node"] != req["node"] or len(nodes) != k or len(scores) != k:
            return False
        if any(not isinstance(v, int) or not 0 <= v < n for v in nodes):
            return False
        if len(set(nodes)) != k or req["node"] in nodes:
            return False
        s = np.asarray(scores, np.float64)
        if not np.isfinite(s).all() or (np.diff(s) > 0).any():
            return False
        ver = body["version"]
        return (isinstance(ver, int) and ver >= 0
                and isinstance(body["error_bound"], (int, float)))
    except (KeyError, TypeError, ValueError):
        return False


def compare(checked, truth: dict, *, walks: int, eps_a: float) -> dict:
    """Numbers over ``checked`` = ``[(req, body), ...]`` of well-formed
    answers; ``truth[(version, u)]`` is the exact row of ``u``."""
    zs, errs, rank_z, deficits = [], [], [], []
    for req, body in checked:
        u = req["node"]
        t = np.array(truth[(body["version"], u)], np.float64)
        t[u] = -np.inf
        nodes = np.asarray(body["topk_nodes"])
        est = np.asarray(body["topk_scores"], np.float64)
        tv = t[nodes]
        best = -np.sort(-t)[: len(nodes)]
        sigma = np.sqrt((tv + T_FLOOR) / walks)
        zs.append((est - tv) / sigma)
        errs.append(np.abs(est - tv))
        deficits.append(best - tv)
        rank_z.append((best - tv) / np.sqrt((best + T_FLOOR) / walks))
    if not zs:
        return {}
    return {
        "bound_over_eps": max(body["error_bound"] for _, body in checked) / eps_a,
        "err_over_bound": float(np.concatenate(errs).max() / eps_a),
        "deficit_over_bound": float(np.concatenate(deficits).max() / eps_a),
        "z_max": float(np.abs(np.concatenate(zs)).max()),
        "z_rms": float(np.sqrt(np.mean(np.concatenate(zs) ** 2))),
        "rank_z": float(np.concatenate(rank_z).max()),
    }

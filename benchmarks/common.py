"""Shared benchmark utilities: timed runs + CSV emission + JSON export."""
from __future__ import annotations

import json
import time

import numpy as np

import jax

ROWS: list[str] = []
RESULTS: dict = {}  # structured results (e.g. the serve suite's qps numbers)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


PROMOTED = ("serve", "dynamic", "abserror", "service", "stream")


def write_json(path: str, *, quick: bool, suites: list[str]) -> None:
    """Machine-readable dump: structured RESULTS + every emitted CSV row.

    Promoted suite blocks (the top-level keys CI acceptance gates read)
    from an EXISTING artifact at ``path`` are carried forward when the
    current run didn't produce them — so ``bench_serve --backend sharded``
    followed by ``bench_service`` compose one artifact instead of each
    leg nulling out the others' rows.
    """
    rows = []
    for row in ROWS:
        name, us, derived = row.split(",", 2)
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": derived})
    payload = dict(
        quick=quick,
        suites=suites,
        backend=jax.default_backend(),
        results=dict(RESULTS),
        rows=rows,
    )
    prior = read_prior_json(path)
    for key in PROMOTED:  # artifacts CI gates read at the top level
        if key in RESULTS:
            payload[key] = RESULTS[key]
        elif key in prior:  # preserved from the last run that had it
            payload[key] = prior[key]
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {path}", flush=True)


def read_prior_json(path: str) -> dict:
    """The existing artifact at ``path``, or {} (missing/corrupt)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def timed(fn, *args, reps: int = 1, **kwargs):
    """(result, seconds/rep) with block_until_ready on jax outputs."""
    out = fn(*args, **kwargs)  # warmup/compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    return out, (time.time() - t0) / reps


def pick_query_nodes(in_deg: np.ndarray, k: int, seed: int = 0) -> np.ndarray:
    """Paper protocol: uniform over nodes with nonzero in-degree."""
    rng = np.random.default_rng(seed)
    cand = np.where(in_deg > 0)[0]
    return rng.choice(cand, size=min(k, len(cand)), replace=False)

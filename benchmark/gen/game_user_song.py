"""The GLMix problem of ``bench.py::_gen_cd_arrays``: ratings with a fixed
effect and a per-user and a per-song random effect, drawn on the host.

Every row has ``dim_fixed`` dense standard-normal columns (shard ``fixed``)
and ``dim_random`` (shard ``item``), picks its user and its song independently
by a Zipf law of exponent ``key_skew`` over the ranks (probability ~
``1 / rank**key_skew``: at exponent 1 and 180,000 users the head user holds
about 8% of the rows and three users in four hold under ten), and draws its
label from the planted model: fixed coefficients N(0, 1), per-user N(0, 1),
per-song 0.7 N(0, 1) on the ``item`` columns.

What ``--seed`` draws is ``gen/common.py``'s rule: with a ``problem_seed`` in
the workload file the whole problem (columns, ids, planted model, labels)
comes from that number and ``--seed`` draws each column's sign in both shards,
the planted coefficients mirrored with it, so that every product
``x_ij w_j``, every label and every solve's work keep their values. Without
one the seed draws the problem. numpy's generator, so that a CPU test and a
chip run hold the same data.
"""

from __future__ import annotations

import numpy as np


def _zipf(rng, n: int, ranks: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, ranks + 1, dtype=np.float64) ** exponent
    return rng.choice(ranks, size=n, p=p / p.sum()).astype(np.int64)


def generate(seed: int, workload: dict, config: dict) -> dict:
    """``{"shards": {"fixed": (rows, dim_fixed), "item": (rows, dim_random)},
    "ids": {"userId", "songId": (rows,) int64}, "y": (rows,) float32,
    "planted": {"fixed", "userId", "songId"}}`` on the host."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    n = int(workload["rows"])
    users, songs = int(workload["users"]), int(workload["songs"])
    d_fixed, d_random = int(config["dim_fixed"]), int(config["dim_random"])
    fixed = workload.get("problem_seed")
    rng = np.random.default_rng(seed if fixed is None else int(fixed))
    w_fixed = rng.normal(size=d_fixed).astype(np.float32)
    w_user = rng.normal(size=(users, d_random)).astype(np.float32)
    w_song = (0.7 * rng.normal(size=(songs, d_random))).astype(np.float32)
    xf = rng.normal(size=(n, d_fixed)).astype(np.float32)
    xi = rng.normal(size=(n, d_random)).astype(np.float32)
    skew = float(workload["key_skew"])
    user = _zipf(rng, n, users, skew)
    song = _zipf(rng, n, songs, skew)
    margin = (xf @ w_fixed + np.einsum("nd,nd->n", xi, w_user[user])
              + np.einsum("nd,nd->n", xi, w_song[song]))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float32)
    if fixed is not None:
        signs = np.random.default_rng(seed)
        sf = np.where(signs.random(d_fixed) < 0.5, -1.0, 1.0).astype(np.float32)
        si = np.where(signs.random(d_random) < 0.5, -1.0, 1.0).astype(
            np.float32)
        xf, xi = xf * sf, xi * si
        w_fixed, w_user, w_song = w_fixed * sf, w_user * si, w_song * si
    return {"shards": {"fixed": xf, "item": xi},
            "ids": {"userId": user, "songId": song}, "y": y,
            "planted": {"fixed": w_fixed, "userId": w_user,
                        "songId": w_song}}

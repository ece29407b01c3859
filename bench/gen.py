"""Seeded generator of the table, the benchmark's own copy, so that no
change to the program can move the yardstick.

:func:`seed_spreader` is Gan & Tao's seed spreader (SIGMOD 2015) as
their experiments define it for SS-simden, the data GriT-DBSCAN
(arXiv:2210.07580) section 5.1 uses: a ball about the walker, a shift
of fixed length in a random direction, restarts per point.

The table is drawn from the configuration's own ``data.seed``, so every
run of a cell holds the same table, the same grid census and therefore
the same compiled programs; ``--seed`` draws the order of its rows.
"""

from __future__ import annotations

import numpy as np


def seed_spreader(n: int, d: int, *, restarts: float, c_reset: int,
                  r_vicinity: float, r_shift: float, noise_frac: float,
                  domain: float, seed: int) -> np.ndarray:
    """n points in [0, domain]^d by Gan & Tao's seed spreader (SS-simden).

    A spreader walks the domain.  Each step it emits one point uniform
    in the ball of radius ``r_vicinity`` about its location; after
    ``c_reset`` points at one location it shifts by ``r_shift`` in a
    uniformly random direction; before any step it restarts at a uniform
    location with probability ``restarts / (n (1 - noise_frac))``, which
    starts a new cluster.  A ``noise_frac`` share of the n points is
    uniform noise over the domain."""
    rng = np.random.default_rng(seed)
    n_noise = int(round(n * noise_frac))
    n_sig = n - n_noise
    restart = rng.uniform(size=n_sig) < restarts / (n * (1 - noise_frac))
    restart[0] = True
    out = np.empty((n_sig, d), dtype=np.float64)
    loc = np.zeros(d)
    i = at_loc = 0
    while i < n_sig:
        if restart[i]:
            loc = rng.uniform(0, domain, size=d)
            at_loc = 0
        nxt = np.flatnonzero(restart[i + 1:i + c_reset - at_loc])
        m = int(nxt[0]) + 1 if len(nxt) else min(c_reset - at_loc, n_sig - i)
        u = rng.normal(size=(m, d))
        u /= np.linalg.norm(u, axis=1, keepdims=True)
        r = r_vicinity * rng.uniform(size=(m, 1)) ** (1.0 / d)
        out[i:i + m] = np.clip(loc + u * r, 0, domain)
        i += m
        at_loc += m
        if at_loc == c_reset:
            v = rng.normal(size=d)
            loc = np.clip(loc + r_shift * v / np.linalg.norm(v), 0, domain)
            at_loc = 0
    noise = rng.uniform(0, domain, size=(n_noise, d))
    return np.concatenate([out, noise], axis=0)


def make_table(config: dict, seed: int) -> np.ndarray:
    """The configuration's table, rows in an order drawn from ``seed``.

    Float64 integer coordinates (``data.round``): integer coordinates
    below 2^24 and their squared distances are exact in float32 and in
    float64, so exactness is a comparison with limit 0."""
    data = config["data"]
    if data["variant"] != "simden":
        raise ValueError(f"unknown seed-spreader variant {data['variant']!r}")
    pts = seed_spreader(
        data["n"], data["d"],
        restarts=data["restarts"], c_reset=data["c_reset"],
        r_vicinity=data["r_vicinity"], r_shift=data["r_shift"],
        noise_frac=data["noise_frac"], domain=data["domain"],
        seed=data["seed"])
    if data["round"]:
        pts = np.rint(pts)
    return pts[np.random.default_rng(seed).permutation(len(pts))]

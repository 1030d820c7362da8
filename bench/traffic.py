"""The benchmark's one traffic generator.

It reads a mix from ``bench/traffic/<name>.json`` and hands its
``request`` parameters to the request kind they name,
``bench/requests/<kind>.py``, which makes every request of a run from
``--seed``: the same seed gives the same requests.  A request is a small
key; the kind's ``payload`` turns a key into the arrays the system under
test is handed, so the correctness check can make any sampled request
again after the window.

A request kind module defines ``Requests(cfg, request, seed)`` with:

* ``variants``: the variants every request is profiled as, in turn;
* ``keys()``: request keys in the order the run issues them, never
  repeating; the first is the warm-up request;
* ``payload(key)``: the arrays of a request;
* ``spec(payload, variant, label)``: the ``WorkloadSpec`` the window's
  ``Session.profile`` call is handed;
* ``launch(payload)``: the shape of its kernel launch, for the work counts
  in ``bench/work/<kernel>.py``.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import load_module

BENCH = Path(__file__).resolve().parent
TRAFFIC_DIR = BENCH / "traffic"
REQUESTS_DIR = BENCH / "requests"


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def zipf_probabilities(n: int, exponent: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -float(exponent)
    return p / p.sum()


def requests(cfg: dict, traffic: dict, seed: int):
    """The requests of mix ``traffic`` for one configuration and seed."""
    req = traffic["request"]
    kind = load_module(REQUESTS_DIR / f"{req['kind']}.py")
    return kind.Requests(cfg, req, int(seed))

"""Plain reference of deepseek-v3-moe-router: DeepSeek-V3's router, in numpy.

The router of one MoE layer (``scoring_func`` sigmoid, ``topk_method``
noaux_tc), as the public modelling code defines it: logits = hidden @
gate weight, scores = sigmoid(logits); experts are *selected* on
scores + ``e_score_correction_bias``: a group of ``n_routed_experts /
n_group`` experts scores the sum of its two best biased scores, the
``topk_group`` best groups are kept, and the ``num_experts_per_tok`` best
biased scores inside them are taken.  Ties go to the lower index (a
stable sort), as the program's ``jax.lax.top_k`` breaks them.  The count
commits the ids token-major, 32 to a commit group and 1,024 to a wave,
padded to a tile of 2,048 with unique ids past the last expert.

The reference routes in the precision it is given (float64 for the
check) from the same bf16 hidden states, float32 gate weight and bias
the program was handed, read back off the clock.

A float32 router may rightly route a token either way where the float64
margin at the group cut (4th against 5th group score) or at the expert
cut (8th against 9th biased score inside the kept groups) is below
``DELTA``.  For those tokens alone the reference takes the program's
choice, got by running the program's router again on the device for that
batch (it is deterministic), and only where that choice is a valid
selection within ``DELTA`` of the float64 optimum: at most ``topk_group``
groups, each within ``DELTA`` of every group left out, and each chosen
expert within ``DELTA`` of every expert of those groups left out.
Otherwise it keeps its own choice, so any other difference shows as a
``counter_gap``.  It logs, per verdict, how many tokens lay within
``DELTA`` of a cut and how many it borrowed.

``DELTA``: the largest difference between the program's float32 logits
(``Precision.HIGHEST``) and float64 logits of the same inputs, over two
16,384-token batches through each of the four layers' routers on a TPU
v5e, was 3.40e-6 (2.44e-6 to 3.40e-6 a batch; ``MAX_LOGIT_ERR``).  A
score moves by at most a quarter of its logit's error (sigmoid' <= 1/4)
and a group score, a sum of two, by at most half, so every cut moves by
at most ``MAX_LOGIT_ERR / 2``; ``DELTA`` is ten times that, a safety
factor of 10.  About 25 tokens a batch lie within ``DELTA`` of a cut and
about 0.2 a batch are routed otherwise by the chip, all of them borrowed.
A router at the chip's default precision (one bf16 pass) errs by 7.3e-3
to 8.3e-3 in a logit, about 500 times ``DELTA``, and comes out not
correct.
"""

from __future__ import annotations

import itertools
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MAX_LOGIT_ERR = 3.4e-6
DELTA = 10 * MAX_LOGIT_ERR / 2
_BLOCK = 1024          # tokens routed per block (59 MB of float64)
_WORKERS = min(8, len(os.sched_getaffinity(0)))


def launch(cfg: dict, payload: dict, variant) -> dict:
    n = cfg["tokens_per_batch"] * cfg["num_experts_per_tok"]
    return {**cfg["launch"], "job_class": cfg["job_class"],
            "bytes_read": float(n * cfg["bytes_per_id"])}


def _desc(a: np.ndarray) -> np.ndarray:
    """Indices that sort each row descending, ties to the lower index."""
    return np.argsort(-a, axis=-1, kind="stable")


def route(cfg: dict, x, w, bias, dtype):
    """(ids (T, k), near (T,) bool, groups (T, G), choice (T, E)): the
    reference's selection, which tokens lie within ``DELTA`` of a cut,
    and the group and biased expert scores it selected on.  Blocks of
    tokens are routed on up to 8 threads, each with a one-thread BLAS
    (numpy lets go of the interpreter lock), so that the check stays
    short."""
    from threadpoolctl import threadpool_limits

    f = np.dtype(dtype).type
    g, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    t, e = x.shape[0], w.shape[1]
    ids = np.empty((t, k), np.int64)
    near = np.empty(t, bool)
    groups = np.empty((t, g), dtype)
    choice = np.empty((t, e), dtype)
    wd, bd = np.asarray(w, dtype), np.asarray(bias, dtype)

    def block(s: int) -> None:
        logits = np.asarray(x[s:s + _BLOCK], dtype) @ wd
        c = f(1) / (f(1) + np.exp(-logits)) + bd
        gs = np.sort(c.reshape(-1, g, e // g), axis=-1)[..., -2:].sum(-1)
        gorder = _desc(gs)
        kept = np.zeros(gs.shape, bool)
        np.put_along_axis(kept, gorder[:, :kg], True, axis=1)
        masked = np.where(np.repeat(kept, e // g, axis=1), c, -np.inf)
        eorder = _desc(masked)
        ids[s:s + _BLOCK] = eorder[:, :k]
        rows = np.arange(gs.shape[0])[:, None]
        gcut = gs[rows, gorder[:, kg - 1:kg]] - gs[rows, gorder[:, kg:kg + 1]]
        ecut = (masked[rows, eorder[:, k - 1:k]]
                - masked[rows, eorder[:, k:k + 1]])
        near[s:s + _BLOCK] = (gcut[:, 0] < DELTA) | (ecut[:, 0] < DELTA)
        groups[s:s + _BLOCK] = gs
        choice[s:s + _BLOCK] = c

    starts = range(0, t, _BLOCK)
    with threadpool_limits(1), \
            ThreadPoolExecutor(min(_WORKERS, len(starts))) as pool:
        list(pool.map(block, starts))
    return ids, near, groups, choice


def within_delta(chosen, groups, choice, cfg: dict) -> bool:
    """Whether ``chosen`` (one token's k expert ids) is a valid selection
    within ``DELTA`` of the optimum of its float64 ``groups`` and biased
    ``choice`` scores: some ``topk_group`` groups holding every chosen
    expert, each within ``DELTA`` of every group left out, and each chosen
    expert within ``DELTA`` of every unchosen expert of those groups."""
    g, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    size = choice.shape[0] // g
    chosen = np.asarray(chosen)
    if len(set(chosen.tolist())) != k or chosen.min() < 0 or \
            chosen.max() >= choice.shape[0]:
        return False
    held = sorted(set((chosen // size).tolist()))
    if len(held) > kg:
        return False
    rest = [h for h in range(g) if h not in held]
    for fill in itertools.combinations(rest, kg - len(held)):
        kept = held + list(fill)
        out = [h for h in range(g) if h not in kept]
        if out and groups[kept].min() < groups[out].max() - DELTA:
            continue
        members = np.concatenate([np.arange(h * size, (h + 1) * size)
                                  for h in kept])
        unchosen = np.setdiff1d(members, chosen)
        if unchosen.size == 0 or \
                choice[chosen].min() >= choice[unchosen].max() - DELTA:
            return True
    return False


def program_ids(payload: dict, variant) -> np.ndarray:
    """The program's own choice for the batch, from its router run again
    on the device, off the clock."""
    import jax

    from repro.models import moe

    gen = payload["source"]
    fn = jax.jit(moe.expert_stream(gen.moe_cfg))
    return np.asarray(fn(payload["hidden"], gen.routers[variant]))


def routed_ids(cfg: dict, payload: dict, variant, dtype) -> np.ndarray:
    """The ids the reference commits: its own choice, with the program's
    borrowed for tokens within ``DELTA`` of a cut where valid."""
    t0 = time.perf_counter()
    x, w, bias = payload["source"].host_arrays(payload, variant)
    t1 = time.perf_counter()
    ids, near, groups, choice = route(cfg, x, w, bias, dtype)
    t2 = time.perf_counter()
    borrowed = 0
    if near.any():
        theirs = program_ids(payload, variant)
        for t in np.flatnonzero(near):
            if set(theirs[t].tolist()) != set(ids[t].tolist()) and \
                    within_delta(theirs[t], groups[t], choice[t], cfg):
                ids[t] = theirs[t]
                borrowed += 1
    print(f"deepseek-v3-moe-router reference: request {payload['key']} "
          f"layer {variant}: {int(near.sum())} tokens within DELTA "
          f"{DELTA!r} of a cut, {borrowed} borrowed (read back in "
          f"{t1 - t0:.3f} s, routed in {t2 - t1:.3f} s, borrowing "
          f"{time.perf_counter() - t2:.3f} s)", file=sys.stderr)
    return ids


def degrees(cfg: dict, payload: dict, variant, dtype, refmodel):
    lc = cfg["launch"]
    stream = routed_ids(cfg, payload, variant, dtype).reshape(-1)
    pad = (-stream.size) % lc["tile_ids"]
    stream = np.concatenate([stream, cfg["n_routed_experts"] + np.arange(pad)])
    return refmodel.group_degrees(stream, group=lc["commit_group"],
                                  lanes=lc["wave_lanes"], dtype=dtype)

"""DeepSeek-V3's router (``MoEConfig.scoring == "sigmoid"``, noaux_tc) against
the benchmark's float64 numpy reference, at a small size on the CPU:
hidden 64, 32 experts in 4 groups, 2 groups kept, top-4, 256 tokens, on
seeded random weights and a seeded correction bias.

The program routes in float32, the reference in float64, so the two may
rightly disagree on a token whose float64 margin at a cut is below the
reference's ``DELTA``; everywhere else the ids must be equal.  The gates
agree to a relative 1e-5: the program's float32 logits over 64 products
of unit-scale terms err by about 1e-7 and the sigmoid, the normalisation
and the scaling add a few float32 roundings, so 1e-5 leaves ten times the
error while a router at bf16 precision (relative error about 4e-3) would
fail it.
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe

ROOT = Path(__file__).resolve().parents[1]
CFG = moe.MoEConfig(d_model=64, d_expert=16, num_experts=32, top_k=4,
                    scoring="sigmoid", n_group=4, topk_group=2,
                    routed_scaling_factor=2.5)
# the reference reads the published key names
REF_CFG = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4}
T = 256


def _reference():
    path = ROOT / "bench" / "configs" / "deepseek-v3-moe-router.py"
    spec = importlib.util.spec_from_file_location("dsv3_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _router(seed: int, bias_std: float = 0.02) -> dict:
    r = moe.init_router(jax.random.key(seed), CFG)
    r["bias"] = bias_std * jax.random.normal(jax.random.key(seed + 1),
                                             (CFG.num_experts,))
    return r


def _hidden(seed: int, t: int = T) -> jnp.ndarray:
    return jax.random.normal(jax.random.key(seed), (t, CFG.d_model)
                             ).astype(jnp.bfloat16)


def _host(x, r):
    return np.asarray(x), np.asarray(r["w"]), np.asarray(r["bias"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_route_matches_the_float64_reference(seed):
    r, x = _router(10 * seed), _hidden(10 * seed + 5)
    gates, ids, aux = moe.route({"router": r}, x, CFG)
    ids, gates = np.asarray(ids), np.asarray(gates, np.float64)
    ref_ids, near, _, _ = REF.route(REF_CFG, *_host(x, r), np.float64)
    far = ~near
    assert far.sum() > 0.9 * T
    assert np.array_equal(np.sort(ids[far], 1), np.sort(ref_ids[far], 1))
    # the gates, from the float64 unbiased scores of the program's ids
    xh, w, _ = _host(x, r)
    scores = 1.0 / (1.0 + np.exp(-(xh.astype(np.float64) @ w)))
    want = np.take_along_axis(scores, ids, 1)
    want = 2.5 * want / want.sum(1, keepdims=True)
    np.testing.assert_allclose(gates, want, rtol=1e-5)
    np.testing.assert_allclose(gates.sum(1), 2.5, rtol=1e-5)
    assert float(aux) == 0.0


def test_every_token_routes_inside_its_kept_groups():
    r, x = _router(7, bias_std=0.5), _hidden(8)
    _, ids, _ = moe.route({"router": r}, x, CFG)
    ids = np.asarray(ids)
    size = CFG.num_experts // CFG.n_group
    assert max(len(set(row)) for row in (ids // size).tolist()) <= \
        CFG.topk_group
    assert all(len(set(row)) == CFG.top_k for row in ids.tolist())


def test_ties_go_to_the_lower_index():
    # zero weight: every score is sigmoid(0) + bias; equal biases tie
    r = {"w": jnp.zeros((CFG.d_model, CFG.num_experts), jnp.float32),
         "bias": jnp.zeros((CFG.num_experts,), jnp.float32)}
    _, ids, _ = moe.route({"router": r}, _hidden(3, 8), CFG)
    assert np.asarray(ids).tolist() == [[0, 1, 2, 3]] * 8
    ref_ids, _, _, _ = REF.route(REF_CFG, *_host(_hidden(3, 8), r),
                                 np.float64)
    assert ref_ids.tolist() == [[0, 1, 2, 3]] * 8


def _seed_route(p, x, cfg):
    """``moe.route`` as it was before sigmoid scoring: the softmax path
    must stay bit-identical to it."""
    logits = (x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(
        jax.nn.one_hot(ids[..., 0], cfg.num_experts, dtype=jnp.float32),
        axis=tuple(range(ids.ndim - 1)))
    mean_probs = probs.mean(axis=tuple(range(probs.ndim - 1)))
    aux = cfg.num_experts * jnp.sum(density * mean_probs)
    return gates, ids, aux


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_softmax_path_is_bit_identical_to_the_seed(dtype):
    cfg = moe.MoEConfig(d_model=64, d_expert=16, num_experts=32, top_k=4,
                        dtype=dtype)
    p = moe.init(jax.random.key(4), cfg)
    assert set(p["router"]) == {"w"}
    assert p["router"]["w"].dtype == jnp.dtype(dtype)
    x = _hidden(9)
    for got, want in zip(moe.route(p, x, cfg), _seed_route(p, x, cfg)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_sigmoid_layer_runs_end_to_end():
    cfg = dataclasses.replace(CFG, dtype="float32", capacity_factor=8.0)
    p = moe.init(jax.random.key(0), cfg)
    assert p["router"]["w"].dtype == jnp.float32
    assert np.array_equal(np.asarray(p["router"]["bias"]),
                          np.zeros(cfg.num_experts, np.float32))
    x = _hidden(1, 32).astype(jnp.float32)
    out, aux, disp = moe.apply_local(p, x, cfg)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    assert disp.shape == (32 * cfg.top_k,)


@pytest.mark.parametrize("bad", [
    dict(scoring="relu"), dict(n_group=3), dict(n_group=32),
    dict(topk_group=5), dict(topk_group=1, top_k=9)])
def test_config_refuses_what_is_not_a_selection(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_deepseek_v3_config_carries_the_published_router():
    import json

    from repro.configs import deepseek_v3
    from repro.models.transformer import _moe_cfg

    pub = json.loads((ROOT / "bench" / "configs"
                      / "deepseek-v3-moe-router.json").read_text())
    m = _moe_cfg(deepseek_v3.CONFIG)
    assert (m.d_model, m.num_experts, m.top_k, m.n_group, m.topk_group,
            m.routed_scaling_factor, m.num_shared_experts, m.scoring) == (
        pub["hidden_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["n_group"], pub["topk_group"],
        pub["routed_scaling_factor"], pub["n_shared_experts"],
        pub["scoring_func"])
    assert deepseek_v3.CONFIG.num_layers == pub["published"][
        "num_hidden_layers"]
    small = _moe_cfg(deepseek_v3.CONFIG.reduced())
    assert small.scoring == "sigmoid" and small.n_group <= small.num_experts


# -- the reference's borrowing at a near tie -----------------------------------


def _near_tie():
    """Two tokens whose 4th and 5th biased scores (experts 3 and 4, both in
    kept group 0) differ by a tenth of DELTA.  The weight is zero, so every
    score is sigmoid(0) = 0.5 and the bias alone orders the experts."""
    w = np.zeros((CFG.d_model, CFG.num_experts), np.float32)
    bias = np.full(CFG.num_experts, -0.4, np.float32)
    bias[:8] = [0.5, 0.4, 0.3, 0.2, 0.2 - REF.DELTA / 10, 0.0, -0.1, -0.2]
    bias[8:10] = [0.15, 0.1]           # group 1 is the second kept group
    x = np.zeros((2, CFG.d_model), np.float32)
    return x, w, bias


def test_reference_marks_tokens_near_a_cut():
    x, w, bias = _near_tie()
    bias_far = bias.copy()
    bias_far[4] = 0.1
    ids, near, _, _ = REF.route(REF_CFG, x, w, bias, np.float64)
    assert near.tolist() == [True, True]
    assert ids[0].tolist() == [0, 1, 2, 3]
    _, near_far, _, _ = REF.route(REF_CFG, x, w, bias_far, np.float64)
    assert near_far.tolist() == [False, False]


def test_reference_borrows_a_choice_within_delta_and_nothing_else():
    x, w, bias = _near_tie()
    ids, _, groups, choice = REF.route(REF_CFG, x, w, bias, np.float64)
    order = np.argsort(-choice[0], kind="stable")
    fourth, fifth = order[3], order[4]
    swapped = [e for e in ids[0].tolist() if e != fourth] + [int(fifth)]
    assert REF.within_delta(swapped, groups[0], choice[0], REF_CFG) == (
        choice[0][fourth] - choice[0][fifth] < REF.DELTA)
    # a choice that skips the best expert is never within DELTA
    worst = [e for e in ids[0].tolist() if e != order[0]] + [int(order[5])]
    assert not REF.within_delta(worst, groups[0], choice[0], REF_CFG)
    # a choice outside the kept groups is refused
    assert not REF.within_delta([0, 1, 8, 16], groups[0], choice[0],
                                REF_CFG)
    # repeated experts are refused
    assert not REF.within_delta([0, 0, 1, 8], groups[0], choice[0],
                                REF_CFG)


def test_near_tie_swap_is_borrowed(monkeypatch):
    """A program that picked the other side of a tie within DELTA is
    followed; one whose choice lies beyond DELTA of the optimum is not."""
    x, w, bias = _near_tie()
    # make expert 4 the 4th and 3 the 5th: move the tie into the top-4
    bias[[2, 3, 4]] = [0.2, 0.2 - REF.DELTA / 10, 0.25]
    ids, near, groups, choice = REF.route(REF_CFG, x, w, bias, np.float64)
    tied = [3 if e == 2 else e for e in ids[0].tolist()]
    assert REF.within_delta(tied, groups[0], choice[0], REF_CFG)

    class Source:
        def host_arrays(self, payload, variant):
            return x, w, bias

    theirs = np.array([tied, [0, 1, 2, 8]])
    monkeypatch.setattr(REF, "program_ids", lambda p, v: theirs)
    got = REF.routed_ids(REF_CFG, {"key": 0, "source": Source()}, 3,
                         np.float64)
    assert sorted(got[0].tolist()) == sorted(tied)        # borrowed
    assert sorted(got[1].tolist()) == sorted(ids[1].tolist())   # kept

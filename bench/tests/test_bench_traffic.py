"""The traffic generator: requests follow from the seed alone, no image
or routing batch repeats within a run, and every mix's request kind is a
module found by name."""

import hashlib
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import traffic  # noqa: E402

BIG = 2**31 + 12345   # seeds past 32 signed bits are valid


def _config(name: str, **changes) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return {**cfg, **changes}


HIST = _config("sec5-hist-4mpx", pixels=2048)
MOE = _config("qwen3-moe-router", tokens_per_batch=256)


def _digest(arr) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()


def _payloads(cfg, mix, seed, n):
    gen = traffic.requests(cfg, traffic.load(mix), seed)
    keys = list(itertools.islice(gen.keys(), n))
    return keys, [gen.payload(k) for k in keys]


@pytest.mark.parametrize("cfg,mix,field", [
    (HIST, "solid-pairs", "img"), (MOE, "zipf-routing", "ids")])
def test_same_seed_same_requests(cfg, mix, field):
    k1, p1 = _payloads(cfg, mix, BIG, 20)
    k2, p2 = _payloads(cfg, mix, BIG, 20)
    k3, p3 = _payloads(cfg, mix, BIG + 1, 20)
    assert k1 == k2
    assert all(np.array_equal(a[field], b[field]) for a, b in zip(p1, p2))
    assert [_digest(p[field]) for p in p1] != [_digest(p[field]) for p in p3]


@pytest.mark.parametrize("cfg,mix,field,n", [
    (HIST, "solid-pairs", "img", 2000), (MOE, "zipf-routing", "ids", 400)])
def test_no_request_repeats_within_a_run(cfg, mix, field, n):
    _, payloads = _payloads(cfg, mix, BIG, n)
    digests = {_digest(p[field]) for p in payloads}
    assert len(digests) == n


def test_solid_images_have_one_colour_per_channel():
    """Outside the planted noise blocks: 1 to 64 blocks of 32 pixels."""
    keys, payloads = _payloads(HIST, "solid-pairs", 7, 20)
    counts, places = set(), set()
    for key, p in zip(keys, payloads):
        img = p["img"]
        assert img.shape == (2048, 4) and img.dtype == np.int32
        colour = np.asarray([(key >> (8 * c)) & 255 for c in range(4)])
        blocks = (img.reshape(64, 32, 4) != colour).any(axis=(1, 2))
        assert 1 <= blocks.sum() <= 64
        assert img.min() >= 0 and img.max() < 256
        counts.add(int(blocks.sum()))
        places.add(tuple(np.flatnonzero(blocks)))
    assert len(counts) > 5 and len(places) == 20


def test_noise_blocks_move_the_counters_and_not_e():
    """Two images of one colour but other noise blocks commit other
    streams; at the paper's size e stays near the solid image's."""
    from bench import compare, refmodel

    big = _config("sec5-hist-4mpx")
    ref = compare.config_reference("sec5-hist-4mpx")
    gen = traffic.requests(big, traffic.load("solid-pairs"), BIG)
    key = next(gen.keys())
    for variant, solid_e in (("hist", 32.0), ("hist2", 8.0)):
        deg = ref.degrees(big, gen.payload(key), variant, np.float64,
                          refmodel)
        assert deg.mean() == pytest.approx(solid_e, abs=0.02)
        assert deg.min() < solid_e
    small = traffic.requests(HIST, traffic.load("solid-pairs"), BIG)
    degs = []
    for k in itertools.islice(small.keys(), 6):
        degs.append(ref.degrees(HIST, small.payload(k), "hist2", np.float64,
                                refmodel).tobytes())
    assert len(set(degs)) == 6


def test_a_mix_names_a_request_kind_module():
    for mix in sorted((ROOT / "bench" / "traffic").glob("*.json")):
        kind = traffic.load(mix.stem)["request"]["kind"]
        assert (ROOT / "bench" / "requests" / f"{kind}.py").is_file()


def test_routing_batches_pick_distinct_experts_per_token():
    _, payloads = _payloads(MOE, "zipf-routing", BIG, 10)
    for p in payloads:
        ids = p["ids"].reshape(-1, MOE["num_experts_per_tok"])
        assert ids.shape == (256, 8)
        assert ids.min() >= 0 and ids.max() < MOE["num_experts"]
        assert all(len(set(row)) == 8 for row in ids.tolist())


def test_routing_is_skewed():
    _, payloads = _payloads(MOE, "zipf-routing", 3, 20)
    counts = np.bincount(payloads[0]["ids"], minlength=128)
    # Zipf(1.2) over 128 ranks: the top expert takes far more than 1/128
    assert counts.max() > 4 * counts.mean()

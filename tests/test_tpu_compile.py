"""The profiler's Pallas kernels compile for a TPU v5e at real sizes.

Nothing runs: each test lowers one kernel launch for one chip of a
*described* ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what Mosaic cannot lower (shape casts, block shapes off the
(8, 128) tiling, too much VMEM) exactly as it would on the chip.  The
topology is described inside a fixture — never at import — so every test
worker collects the same tests and only the worker given this file loads
the TPU library.  All of these tests live in this one file for that reason.

The sizes are the chip smoke test's: the paper's largest histogram image
(RGBA, 2^22 px), the MoE dispatch of a 128-expert top-8 router over 8,192
tokens (65,536 ids), a segment sum over 16,384 segments (four segment
blocks) at D=128, and flash attention at (8, 2048, 128) bf16; and the
dsv3-routed cell's two programs, DeepSeek-V3's router with its count and
the device digest of its 16,384-token batch.
"""

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

HBM_BYTES = 16 * 1024**3          # one v5e chip
PIXELS = 1 << 22
IDS = 1 << 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compile_for_chip(topo, monkeypatch):
    """``compile_(fn, *(shape, dtype))`` -> the v5e ``Compiled``.

    The backend this process sees is the CPU, so the kernels' own
    backend check would pick the interpreter: the test steers it to the
    compiled path the chip takes.
    """
    from jax.sharding import SingleDeviceSharding

    from repro import kernels
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()
    return compile_


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()     # Mosaic, not XLA ops
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("reorder", [False, True], ids=["hist", "hist2"])
@pytest.mark.parametrize("mode", ["plain", "instrumented", "weighted"])
def test_histogram_compiles(compile_for_chip, reorder, mode):
    from repro.kernels.histogram import kernel as hk

    img = ((PIXELS, 4), jnp.int32)
    if mode == "weighted":
        compiled = compile_for_chip(
            lambda im, w: hk.histogram_pallas(im, reorder=reorder,
                                              weights=w),
            img, ((PIXELS,), jnp.float32))
    else:
        compiled = compile_for_chip(
            lambda im: hk.histogram_pallas(
                im, reorder=reorder, instrumented=mode == "instrumented"),
            img)
    _check(compiled)


@pytest.mark.parametrize("instrumented", [False, True],
                         ids=["plain", "instrumented"])
def test_scatter_add_compiles(compile_for_chip, instrumented):
    from repro.kernels.scatter_add import kernel as sk

    compiled = compile_for_chip(
        lambda v, i: sk.scatter_add_pallas(v, i, 16384,
                                           instrumented=instrumented),
        ((IDS, 128), jnp.float32), ((IDS,), jnp.int32))
    _check(compiled)


def test_bincount_compiles(compile_for_chip):
    from repro.kernels.scatter_add import kernel as sk

    _check(compile_for_chip(lambda i: sk.bincount_pallas(i, 128),
                            ((IDS,), jnp.int32)))


def test_flash_attention_compiles(compile_for_chip):
    from repro.kernels.flash_attention import kernel as fk

    qkv = ((8, 2048, 128), jnp.bfloat16)
    _check(compile_for_chip(fk.flash_attention_pallas, qkv, qkv, qkv))


def test_routed_count_program_compiles(compile_for_chip):
    """DeepSeek-V3's router and the expert-load count in one program, at
    the published widths: 16,384 bf16 tokens of 7,168, 256 experts in 8
    groups, top-8 of the best 4 (131,072 ids)."""
    from repro.kernels.scatter_add import ops
    from repro.models import moe

    cfg = moe.MoEConfig(d_model=7168, d_expert=2048, num_experts=256,
                        top_k=8, scoring="sigmoid", n_group=8, topk_group=4,
                        routed_scaling_factor=2.5)
    program = ops.count_program(moe.expert_stream(cfg), 256)
    compiled = compile_for_chip(
        lambda x, w, b: program(x, {"w": w, "bias": b}),
        ((16384, 7168), jnp.bfloat16), ((7168, 256), jnp.float32),
        ((256,), jnp.float32))
    _check(compiled)


def test_device_digest_compiles(compile_for_chip):
    from repro.kernels import digest

    compiled = compile_for_chip(
        lambda x, w, b: digest._digest_all((x, w, b)),
        ((16384, 7168), jnp.bfloat16), ((7168, 256), jnp.float32),
        ((256,), jnp.float32))
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES

"""repro.lint — symbolic tracer, static derivation, KERN rules, CLI.

The headline guarantee under test: for affine kernels (hist/hist2) the
statically derived counters are **bit-for-bit** the trace provider's,
with zero kernel executions and the session's collection stats pinned
to zero.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro import audit as audit_mod
from repro import lint as lint_mod
from repro.analysis import Session, WorkloadSpec
from repro.analysis.providers.trace import TraceProvider
from repro.core import timing
from repro.data.images import make_image
from repro.kernels.histogram import kernel as hist_kernel
from repro.kernels.histogram import ops as hist_ops
from repro.lint import registry as lint_registry_mod
from repro.lint import symbolic
from repro.lint.analysis import (DATA_DEPENDENT, STATIC, degree_stats,
                                 derive_counters, derive_stream,
                                 target_from_spec)
from repro.lint.tracing import analyze_callable

PROBE_PIXELS = lint_registry_mod.PROBE_PIXELS


@pytest.fixture(scope="module")
def sess():
    return Session("v5e")


def _probe_spec(variant, kind="solid", pixels=PROBE_PIXELS):
    img = make_image(kind, pixels, seed=0)
    return WorkloadSpec.from_histogram(
        img, label=f"{variant}-{kind}", variant=variant,
        waves_per_tile=8, overhead_cycles=2500.0)


# -- symbolic expressions ----------------------------------------------------


_I32 = np.dtype("int32")


def test_symbolic_affine_evaluation():
    # (iota(8) * 4 + pid) % 8 evaluated exactly
    iota = symbolic.Iota(shape=(8,), dtype=_I32, dim=0)
    four = symbolic.Const(shape=(), dtype=_I32, value=np.int32(4))
    eight = symbolic.Const(shape=(), dtype=_I32, value=np.int32(8))
    pid = symbolic.ProgramId(shape=(), dtype=_I32, axis=0)
    mul = symbolic.Elem(shape=(8,), dtype=_I32, op="mul",
                        args=(iota, four))
    add = symbolic.Elem(shape=(8,), dtype=_I32, op="add", args=(mul, pid))
    expr = symbolic.Elem(shape=(8,), dtype=_I32, op="rem",
                         args=(add, eight))
    got = symbolic.evaluate(expr, {("pid", 0): 3})
    np.testing.assert_array_equal(got, (np.arange(8) * 4 + 3) % 8)


def test_symbolic_trunc_division_matches_lax():
    # lax div/rem truncate toward zero; numpy floors — the evaluator
    # must follow lax
    num = symbolic.Const(shape=(3,), dtype=_I32,
                         value=np.array([-7, 7, -7], np.int32))
    den = symbolic.Const(shape=(3,), dtype=_I32,
                         value=np.array([2, -2, -2], np.int32))
    div = symbolic.Elem(shape=(3,), dtype=_I32, op="div", args=(num, den))
    rem = symbolic.Elem(shape=(3,), dtype=_I32, op="rem", args=(num, den))
    np.testing.assert_array_equal(symbolic.evaluate(div, {}), [-3, -3, 3])
    np.testing.assert_array_equal(symbolic.evaluate(rem, {}), [-1, 1, -1])


def test_symbolic_data_refs_and_program_axes():
    data = symbolic.Data(shape=(4,), dtype=_I32, ref=2, name="ref2")
    pid = symbolic.ProgramId(shape=(), dtype=_I32, axis=1)
    expr = symbolic.Elem(shape=(4,), dtype=_I32, op="add",
                         args=(data, pid))
    assert symbolic.data_refs(expr) == {2}
    assert symbolic.program_axes(expr) == {1}
    assert symbolic.data_refs(pid) == set()


# -- jaxpr tracing: structure ------------------------------------------------


def test_hist_kernel_model_structure():
    target = lint_registry_mod.build_target("hist")
    models = analyze_callable(target.fn, *target.args, name="hist")
    assert len(models) == 1
    m = models[0]
    # one step per tile of 2048 px (8 waves of 1024 commits)
    assert m.grid == (PROBE_PIXELS // hist_kernel.DEFAULT_TILE,)
    site = m.sites[0]
    assert site.kind == "one_hot_popcount"
    assert site.rmw and site.num_bins == 1024 and site.row_elems == 1
    # the @pl.when(pid==0) zero-init is seen as an init guard on axis 0
    assert m.init_guards.get(site.ref) == {0}


def test_factored_one_hot_product_is_one_popcount_site(sess):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        b = x_ref[...]                       # (8, 128) bin ids in [0, 64)
        iota = jax.lax.broadcasted_iota(jnp.int32, (8, 8, 128), 1)
        hi = (jax.lax.div(b, 8)[:, None, :] == iota).astype(jnp.bfloat16)
        lo = (jax.lax.rem(b, 8)[:, None, :] == iota).astype(jnp.bfloat16)
        joint = jax.lax.dot_general(
            hi, lo, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        o_ref[...] += joint.sum(axis=0).astype(jnp.int32)

    def launch(x):
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int32),
            interpret=True,
        )(x)

    x = (np.arange(16 * 128, dtype=np.int32) * 7 % 64).reshape(16, 128)
    models = analyze_callable(launch, x, name="factored")
    (site,) = models[0].sites
    assert site.kind == "one_hot_popcount" and site.row_elems == 1
    assert site.num_bins == 64 and site.stream_len == 8 * 128
    # hi * 8 + lo is the bin id itself, element for element
    np.testing.assert_array_equal(
        symbolic.evaluate(site.stream, {("ref", 0): x[8:]}), x[8:])
    np.testing.assert_array_equal(
        np.asarray(launch(x)).reshape(-1), np.bincount(x.reshape(-1)))
    target = lint_mod.LintTarget(
        label="factored", fn=launch, args=(x,), operands=(x,),
        spec=None, module=None, job_class=timing.POPC, waves_per_tile=1)
    findings = lint_mod.evaluate_target(target, sess, models=models)
    assert not any(f.rule_id == "KERN002" for f in findings), findings


def test_unguarded_accumulation_fires_kern003(sess):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        # rmw accumulate with NO pl.when(pid==0) zero-init, output block
        # independent of the grid axis: a cross-step race
        o_ref[...] += jnp.sum(x_ref[...], axis=0)

    def launch(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((256, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
            interpret=True,
        )(x)

    x = jax.ShapeDtypeStruct((1024, 8), jnp.float32)
    models = analyze_callable(launch, x, name="unguarded")
    target = lint_mod.LintTarget(
        label="unguarded", fn=launch, args=(x,), operands=(None,),
        spec=None, module=None, job_class=timing.FAO, waves_per_tile=8)
    findings = lint_mod.evaluate_target(target, sess, models=models)
    assert any(f.rule_id == "KERN003" and f.severity == "error"
               for f in findings), findings


def test_while_swap_fires_kern004(sess):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        def body(i):
            o_ref[0] = x_ref[i]      # store inside a while body: retry shape
            return i + 1

        jax.lax.while_loop(lambda i: i < 4, body, 0)

    def launch(x):
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8,), lambda i: (0,))],
            out_specs=pl.BlockSpec((8,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
            interpret=True,
        )(x)

    x = jax.ShapeDtypeStruct((16,), jnp.float32)
    models = analyze_callable(launch, x, name="retry")
    assert models[0].while_has_swap
    target = lint_mod.LintTarget(
        label="retry", fn=launch, args=(x,), operands=(None,),
        spec=None, module=None, job_class=timing.FAO, waves_per_tile=8)
    findings = lint_mod.evaluate_target(target, sess, models=models)
    assert any(f.rule_id == "KERN004" for f in findings), findings


# -- static derivation: the bit-for-bit guarantee ----------------------------


@pytest.mark.parametrize("variant", ["hist", "hist2"])
def test_static_stream_equals_committed_stream(variant):
    spec = _probe_spec(variant, "solid")
    target = target_from_spec(spec)
    models = analyze_callable(target.fn, *target.args, name=variant)
    site = models[0].sites[0]
    deriv = derive_stream(models[0], site, target.operands)
    assert deriv.classification == STATIC, deriv.reasons
    img = spec.kernel.params["img"]
    # site.num_bins is the flattened output width (256 bins x 4 channels);
    # the ops-level synthesis takes the per-channel bin count
    assert site.num_bins == 256 * img.shape[-1]
    expected = hist_ops.committed_index_stream(
        img, num_bins=256, variant=variant)
    np.testing.assert_array_equal(deriv.stream, expected)


@pytest.mark.parametrize("variant", ["hist", "hist2"])
def test_uniform_probe_is_data_dependent(variant):
    # non-constant operand contents cannot be proved: the lint must
    # classify them for the dynamic path, never guess a stream
    spec = _probe_spec(variant, "uniform")
    target = target_from_spec(spec)
    models = analyze_callable(target.fn, *target.args, name=variant)
    deriv = derive_stream(models[0], models[0].sites[0], target.operands)
    assert deriv.classification == DATA_DEPENDENT
    assert deriv.stream is None


@pytest.mark.parametrize("variant", ["hist", "hist2"])
def test_derived_counters_bitwise_equal_trace_provider(variant):
    sess = Session("v5e")
    spec = _probe_spec(variant)
    derived, deriv = derive_counters(spec)
    assert derived is not None and deriv.is_static
    expected = TraceProvider().collect(spec, sess.device)
    for field in vars(expected):
        a, b = getattr(derived, field), getattr(expected, field)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype, field
            np.testing.assert_array_equal(a, b, err_msg=field)
        else:
            assert a == b, field
    # the whole derivation ran zero collections
    assert sess.stats == {"collected": 0, "memo_hits": 0, "disk_hits": 0,
                          "batch_calls": 0}


def test_degree_floor_separates_hist_from_hist2():
    stats = {}
    for variant in ("hist", "hist2"):
        target = target_from_spec(_probe_spec(variant))
        models = analyze_callable(target.fn, *target.args, name=variant)
        d = degree_stats(derive_stream(models[0], models[0].sites[0],
                                       target.operands))
        stats[variant] = d
    assert stats["hist"].mean_degree > stats["hist"].floor_degree
    assert stats["hist2"].mean_degree == pytest.approx(
        stats["hist2"].floor_degree)


# -- rule firing over the registry -------------------------------------------


def test_hist_fires_kern001_error(sess):
    rep = lint_mod.lint_kernel("hist", session=sess)
    f = next(f for f in rep.findings if f.rule_id == "KERN001")
    assert f.severity == "error" and not f.suppressed
    assert f.utilization is not None and f.contention > 1.0


def test_hist2_lints_clean(sess):
    rep = lint_mod.lint_kernel("hist2", session=sess)
    assert rep.active() == []


def test_flash_attention_lints_clean(sess):
    rep = lint_mod.lint_kernel("flash_attention", session=sess)
    assert rep.active() == []


def test_weighted_hist_fires_kern004(sess):
    rep = lint_mod.lint_kernel("hist_weighted", session=sess)
    ids = {f.rule_id for f in rep.active()}
    assert "KERN004" in ids and "KERN001" in ids


def test_scatter_add_kern002_suppressed_in_source(sess):
    # scatter_add/kernel.py carries `# repro: noqa KERN002`
    rep = lint_mod.lint_kernel("scatter_add", session=sess)
    k2 = [f for f in rep.findings if f.rule_id == "KERN002"]
    assert k2 and all(f.suppressed for f in k2)
    k5 = [f for f in rep.findings if f.rule_id == "KERN005"]
    assert k5 and not any(f.suppressed for f in k5)
    res = [r for r in rep.to_sarif()["runs"][0]["results"]
           if r["ruleId"] == "KERN002"]
    assert res[0]["suppressions"] == [{"kind": "inSource"}]


def test_data_dependent_kernels_emit_kern005_with_spec(sess):
    rep = lint_mod.lint_kernel("moe_dispatch", session=sess)
    f = next(f for f in rep.findings if f.rule_id == "KERN005")
    assert f.severity == "note"
    assert f.spec is not None        # carries the dynamic-audit workload
    assert f.site.classification == DATA_DEPENDENT


def test_session_lint_front_door(sess):
    rep = sess.lint(["hist2"])
    assert rep.active() == []
    rep = sess.lint(_probe_spec("hist"))   # a WorkloadSpec routes through
    assert any(f.rule_id == "KERN001" for f in rep.findings)


# -- unified audit/lint reporting --------------------------------------------


def test_sarif_catalog_spans_audit_and_kern_rules(sess):
    rep = lint_mod.lint_kernel("hist", session=sess)
    sarif = rep.to_sarif()
    ids = [d["id"] for d in sarif["runs"][0]["tool"]["driver"]["rules"]]
    for rid in ("ATOM001", "BANK001", "GEOM001", "AUDIT000",
                "KERN001", "KERN005"):
        assert rid in ids
    for r in sarif["runs"][0]["results"]:
        assert ids[r["ruleIndex"]] == r["ruleId"]


def test_merge_sarif_reindexes_by_rule_id(sess):
    lint_doc = lint_mod.lint_kernel("hist", session=sess).to_sarif()
    audit_doc = {"runs": [{"results": [
        {"ruleId": "ATOM001", "ruleIndex": 99, "level": "error",
         "message": {"text": "x"}}]}]}
    merged = audit_mod.merge_sarif([audit_doc, lint_doc])
    ids = [d["id"] for d in merged["runs"][0]["tool"]["driver"]["rules"]]
    results = merged["runs"][0]["results"]
    assert len(results) == 1 + len(lint_doc["runs"][0]["results"])
    for r in results:
        assert ids[r["ruleIndex"]] == r["ruleId"]
    json.dumps(merged)               # serializable end to end


def test_attach_advice_rotation_in_paper_band(sess):
    rep = lint_mod.lint_kernel("hist", session=sess)
    audit_mod.attach_advice(rep, sess)
    f = next(f for f in rep.findings if f.rule_id == "KERN001")
    assert f.advice is not None
    assert "rotation" in f.advice["families"]
    # the paper's headline: reordering buys up to ~30%
    assert 1.0 < f.advice["predicted_speedup"] <= 1.30
    assert f.advice["predicted_bottleneck"]
    res = next(r for r in rep.to_sarif()["runs"][0]["results"]
               if r["ruleId"] == "KERN001")
    assert res["properties"]["advise"]["families"] == f.advice["families"]


# -- CLI ---------------------------------------------------------------------


def test_cli_lint_gate(tmp_path, capsys):
    from repro.cli import main as cli_main
    rc = cli_main(["lint", "--kernel", "hist2", "--fail-on", "warning",
                   "--no-artifact"])
    assert rc == 0
    assert "no findings" in capsys.readouterr().out
    out_path = tmp_path / "lint.sarif"
    rc = cli_main(["lint", "--kernel", "hist", "--format", "sarif",
                   "--output", str(out_path), "--no-artifact"])
    assert rc == 1                   # KERN001 is an error at default gate
    doc = json.loads(out_path.read_text())
    assert any(r["ruleId"] == "KERN001"
               for r in doc["runs"][0]["results"])


def test_cli_lint_list(capsys):
    from repro.cli import main as cli_main
    assert cli_main(["lint", "--list"]) == 0
    out = capsys.readouterr().out.split()
    assert "hist" in out and "flash_attention" in out

"""repro.analysis: the jaxpr invariant auditor, the AST lint engine, and
the full sampler × solver × backend matrix audit.

Three layers, matching the subsystem's own:

* rule unit tests on tiny hand-built traces (each rule flags exactly the
  anti-pattern it names, and nothing else);
* lint-engine tests on temp files (each rule, the allowlist, inline
  ``# analysis: allow(...)`` suppression, syntax-error reporting);
* the acceptance matrix: every sampler × solver × backend fit jaxpr
  passes its cell's ``MaxIntermediate``/``CollectiveBound`` rules, every
  solver × backend predict jaxpr additionally passes ``NoHostSync``, and
  the seeded n×n violation is always caught — the regression test that
  keeps the CI gate non-vacuous.
"""
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (AccumDtype, BareExcept, CollectiveBound,
                            CompileCounter, FrozenConfigMutation,
                            MaxIntermediate, NoCollectives, NoDirectGram,
                            NoHostSync, NoNumpyRandom, NoPrngLiteral,
                            assert_audit, audit_fit, audit_jaxpr,
                            audit_predict, cell_bound, collective_sizes,
                            iter_eqns, lint_file, lint_paths,
                            max_intermediate_size, seeded_violation_findings,
                            smoke_cells)
from repro.analysis.matrix import _base_config, default_n
from repro.core.precision import Precision

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


# ------------------------------------------------------- jaxpr rule units

class TestJaxprRules:
    def test_max_intermediate_flags_at_bound_and_passes_below(self):
        jx = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.ones((8, 4)), jnp.ones((4, 8)))
        # the 8×8 product trips a bound of 64, passes a bound of 65
        found = audit_jaxpr(jx, [MaxIntermediate(64)])
        assert found and all(f.rule == "max-intermediate" for f in found)
        assert "dot_general" in found[0].message
        assert audit_jaxpr(jx, [MaxIntermediate(65)]) == []

    def test_inputs_are_not_flagged_only_products(self):
        # identity: the (big) input flows straight through reshape-free;
        # only values the program CREATES count
        jx = jax.make_jaxpr(lambda a: jnp.sum(a))(jnp.ones((32, 32)))
        assert audit_jaxpr(jx, [MaxIntermediate(32 * 32)]) == []

    def test_iter_eqns_recurses_into_pjit_and_scan(self):
        def f(x):
            def body(c, xi):
                return c + jnp.outer(xi, xi).sum(), None
            out, _ = jax.lax.scan(body, 0.0, x)
            return jax.jit(lambda v: v * 2.0)(out)

        jx = jax.make_jaxpr(f)(jnp.ones((4, 16)))
        paths = {path for _, path in iter_eqns(jx)}
        assert any("scan" in p for p in paths)
        # the outer product lives INSIDE the scan body — a non-recursive
        # walk would miss it
        found = audit_jaxpr(jx, [MaxIntermediate(16 * 16)])
        assert found and "scan" in found[0].where

    def test_collective_bound_and_no_collectives(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("i",))
        psum = jax.shard_map(lambda x: jax.lax.psum(x, "i"), mesh=mesh,
                         in_specs=jax.sharding.PartitionSpec("i"),
                         out_specs=jax.sharding.PartitionSpec())
        jx = jax.make_jaxpr(psum)(jnp.ones((8, 8)))
        assert collective_sizes(jx) == [64]
        assert audit_jaxpr(jx, [CollectiveBound(64)]) == []   # equality passes
        over = audit_jaxpr(jx, [CollectiveBound(63)])
        assert over and over[0].rule == "collective-bound"
        none = audit_jaxpr(jx, [NoCollectives()])
        assert none and none[0].rule == "no-collectives"
        clean = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(4))
        assert audit_jaxpr(clean, [NoCollectives()]) == []
        assert collective_sizes(clean) == []

    def test_no_host_sync_flags_callbacks(self):
        def f(x):
            return jax.pure_callback(
                lambda v: np.asarray(v) * 2.0,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        found = audit_jaxpr(jx, [NoHostSync()])
        assert found and found[0].rule == "no-host-sync"
        assert audit_jaxpr(jax.make_jaxpr(jnp.sin)(1.0), [NoHostSync()]) == []

    def test_accum_dtype_floor(self):
        # bf16 storage floors accumulation at f32 (the MXU rule)
        rule = AccumDtype(Precision(), jnp.bfloat16)
        a = jnp.ones((4, 4), jnp.bfloat16)
        bad = jax.make_jaxpr(
            lambda a, b: jax.lax.dot(a, b,
                                     preferred_element_type=jnp.bfloat16))(a, a)
        found = audit_jaxpr(bad, [rule])
        assert found and found[0].rule == "accum-dtype"
        assert "bfloat16" in found[0].message
        good = jax.make_jaxpr(
            lambda a, b: jax.lax.dot(a, b,
                                     preferred_element_type=jnp.float32))(a, a)
        assert audit_jaxpr(good, [rule]) == []
        # f32 storage with a default policy: f32 accumulation is the floor
        f = jnp.ones((4, 4), jnp.float32)
        ok = jax.make_jaxpr(lambda a, b: a @ b)(f, f)
        assert audit_jaxpr(ok, [AccumDtype(Precision(), jnp.float32)]) == []

    def test_assert_audit_raises_listing_findings(self):
        jx = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.ones((8, 4)), jnp.ones((4, 8)))
        with pytest.raises(AssertionError, match="max-intermediate"):
            assert_audit(jx, [MaxIntermediate(10)], where="unit")
        assert_audit(jx, [MaxIntermediate(10_000)], where="unit")  # clean

    def test_max_intermediate_size_matches_hand_walk(self):
        jx = jax.make_jaxpr(lambda a, b: (a @ b).sum())(
            jnp.ones((8, 4)), jnp.ones((4, 8)))
        assert max_intermediate_size(jx) == 64


# ------------------------------------------------------------- lint units

def _lint_src(tmp_path, source, rules):
    f = tmp_path / "mod.py"
    f.write_text(textwrap.dedent(source))
    return lint_file(f, "pkg/mod.py", rules)


class TestLintRules:
    def test_no_direct_gram(self, tmp_path):
        found = _lint_src(tmp_path, """
            def f(kernel, X, idx, ops):
                a = kernel.gram(X, X)
                b = gram_matrix(kernel, X)
                c = kernel_columns(kernel, X, idx)
                d = ops.columns(X, idx)        # the sanctioned seam
                return a, b, c, d
            """, [NoDirectGram()])
        assert [f.rule for f in found] == ["no-direct-gram"] * 3
        assert {f.line for f in found} == {3, 4, 5}

    def test_no_prng_literal(self, tmp_path):
        found = _lint_src(tmp_path, """
            import jax
            k1 = jax.random.PRNGKey(0)
            k2 = jax.random.key(42)
            k3 = jax.random.key(config.seed)   # derived: fine
            """, [NoPrngLiteral()])
        assert [f.rule for f in found] == ["no-prng-literal"] * 2
        assert {f.line for f in found} == {3, 4}

    def test_no_numpy_random(self, tmp_path):
        found = _lint_src(tmp_path, """
            import numpy as np
            x = np.random.default_rng(0).normal(size=3)
            y = np.zeros(3)                    # non-random numpy: fine
            """, [NoNumpyRandom()])
        assert [f.rule for f in found] == ["no-numpy-random"]

    def test_frozen_config_mutation(self, tmp_path):
        found = _lint_src(tmp_path, """
            def f(config, cfg, other):
                config.p = 3
                cfg.lam += 1.0
                object.__setattr__(config, "p", 3)
                other.p = 3                    # not a config name: fine
                fresh = config.replace(p=3)    # the sanctioned path
                return fresh
            """, [FrozenConfigMutation()])
        assert [f.rule for f in found] == ["frozen-config-mutation"] * 3

    def test_bare_except(self, tmp_path):
        found = _lint_src(tmp_path, """
            try:
                pass
            except:
                pass
            try:
                pass
            except ValueError:
                pass
            """, [BareExcept()])
        assert [f.rule for f in found] == ["bare-except"]
        assert found[0].line == 4

    def test_inline_suppression_same_line_and_line_above(self, tmp_path):
        found = _lint_src(tmp_path, """
            def f(kernel, X):
                a = kernel.gram(X, X)  # analysis: allow(no-direct-gram)
                # analysis: allow(no-direct-gram)
                b = kernel.gram(X, X)
                c = kernel.gram(X, X)          # NOT suppressed
                return a, b, c
            """, [NoDirectGram()])
        assert [f.line for f in found] == [6]

    def test_suppression_is_per_rule(self, tmp_path):
        found = _lint_src(tmp_path, """
            def f(kernel, X):
                return kernel.gram(X, X)  # analysis: allow(bare-except)
            """, [NoDirectGram()])
        assert len(found) == 1             # wrong rule name: no effect

    def test_allowlist_suffix_and_directory(self):
        rule = NoDirectGram()
        assert rule.skips("repro/core/kernels.py")
        assert not rule.skips("repro/api/solvers.py")
        prng = NoPrngLiteral()
        assert prng.skips("repro/launch/train.py")     # "launch/" dir entry
        assert not prng.skips("repro/core/launchpad.py")

    def test_syntax_error_is_a_finding(self, tmp_path):
        f = tmp_path / "broken.py"
        f.write_text("def f(:\n")
        found = lint_file(f, "pkg/broken.py")
        assert len(found) == 1 and found[0].rule == "syntax"

    def test_repo_tree_is_clean(self):
        findings = lint_paths(SRC)
        assert not findings, "\n".join(str(f) for f in findings)


# --------------------------------------- the sampler×solver×backend matrix

FULL_CELLS = list(smoke_cells(full=True))


class TestMatrixAudit:
    @pytest.mark.parametrize(
        "label,config", FULL_CELLS, ids=[lbl for lbl, _ in FULL_CELLS])
    def test_fit_jaxpr_keeps_the_space_envelope(self, label, config):
        findings = audit_fit(config)
        assert not findings, "\n".join(str(f) for f in findings)

    @pytest.mark.parametrize(
        "label,config",
        [(lbl, cfg) for lbl, cfg in smoke_cells()
         if cfg.sampler == "rls_fast"],
        ids=[lbl for lbl, cfg in smoke_cells() if cfg.sampler == "rls_fast"])
    def test_predict_jaxpr_is_host_sync_free(self, label, config):
        findings = audit_predict(config)
        assert not findings, "\n".join(str(f) for f in findings)

    @pytest.mark.smoke
    def test_smoke_cells_fit_clean(self):
        # the exact set the CI smoke lane's CLI step audits — one cell per
        # axis value; kept as a pytest too so local -m smoke covers it
        label, config = next(iter(smoke_cells()))
        assert audit_fit(config) == []

    def test_dense_cells_get_the_dense_bound(self):
        dense = _base_config(sampler="uniform", solver="exact", backend="xla")
        sketched = _base_config(sampler="uniform",
                                solver="nystrom_regularized", backend="xla")
        n = 64
        assert cell_bound(dense, n) == n * n + 1
        assert cell_bound(sketched, n) < n * n
        # pallas bounds are in lane-padded physical units
        pallas = _base_config(sampler="uniform",
                              solver="nystrom_regularized", backend="pallas")
        assert cell_bound(pallas, n) == n * 128 + 1
        # and default_n keeps n·n above the padded bound — the n×n Gram
        # stays detectable in pallas cells
        np_ = default_n(pallas)
        assert np_ * np_ > cell_bound(pallas, np_)

    def test_seeded_violation_is_always_caught(self):
        findings = seeded_violation_findings()
        assert findings, ("the deliberately n×n fit produced NO findings "
                          "— the auditor is vacuous")
        assert all(f.rule == "max-intermediate" for f in findings)
        assert any("64, 64" in f.message for f in findings)

    def test_cli_seed_violation_exits_nonzero(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--seed-violation"]) == 1
        out = capsys.readouterr().out
        assert "findings EXPECTED" in out and "correctly flagged" in out

    def test_cli_lints_exit_zero_on_clean_tree(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--no-jaxpr"]) == 0
        assert "analysis: PASS" in capsys.readouterr().out

    def test_cli_reports_seeded_lint_findings(self, tmp_path, capsys):
        from repro.analysis.__main__ import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text("import jax\nk = jax.random.key(7)\n")
        assert main(["--no-jaxpr", "--src", str(pkg)]) == 1
        out = capsys.readouterr().out
        assert "no-prng-literal" in out and "analysis: FAIL" in out


# ------------------------------------------------------- dynamic compiles

class TestCompileCounter:
    def test_counts_fresh_compile_not_cache_hit(self):
        if not CompileCounter.supported():
            pytest.skip("this jax build does not emit the compile "
                        "duration monitoring event")
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        x = jnp.arange(5.0)
        x6 = jnp.arange(6.0)       # built OUTSIDE the counted blocks — the
        jax.block_until_ready(x6)  # iota itself compiles a tiny program
        with CompileCounter() as cc:
            f(x)
        assert cc.count == 1
        with CompileCounter() as cc2:
            f(x)                               # cache hit: no compile
        assert cc2.count == 0
        with CompileCounter() as cc3:
            f(x6)                              # new shape: recompile
        assert cc3.count == 1

    def test_listener_is_inert_outside_the_block(self):
        if not CompileCounter.supported():
            pytest.skip("this jax build does not emit the compile "
                        "duration monitoring event")
        cc = CompileCounter()
        with cc:
            pass
        jax.jit(lambda x: x - 7.5)(jnp.arange(4.0))   # fresh compile AFTER
        assert cc.count == 0

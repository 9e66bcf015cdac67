"""Tests for ``repro.analyze`` — the static numerics & precision linter.

The seeded-violation tests are the core: plant a known bug (a bf16
contraction accumulating at bf16 inside a spectral-contract scope; a
SiteRule shadowed dead within its own table; an OOB BlockSpec index
map) and assert the analyzer reports exactly that check at exactly that
site/severity.  The clean-tree tests pin the other direction: the
shipped rule tables, site literals, and kernel families produce zero
error-severity findings.
"""
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analyze import (
    ERROR,
    WARNING,
    Finding,
    Suppression,
    dedupe,
    kernels_pass,
    load_suppressions,
    partition,
    rule_table_findings,
    shadowed_entries,
    site_universe,
    sites_pass,
    trace_findings,
)
from repro.analyze.kernels import KernelCall, check_call, tile_bytes
from repro.analyze.sites import orphan_site_findings
from repro.precision.policy import get_policy
from repro.precision.rules import SiteRule

_REPO_SRC = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "src"))

_DIMS = (((1,), (0,)), ((), ()))  # plain matmul dimension_numbers


def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# Seeded violations: the analyzer must catch these
# ---------------------------------------------------------------------------


class TestSeededDataflowViolations:
    def test_bf16_contraction_without_f32_accum_is_an_error(self):
        """The canonical planted bug: a bf16 dot_general inside a
        ``*/spectral/contract`` scope with no f32 accumulation."""

        def bad(x, w):
            with jax.named_scope("fno/layer2/spectral/contract"):
                return jax.lax.dot_general(
                    x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), _DIMS)

        findings = trace_findings(
            bad, (_sds(4, 8), _sds(8, 4)), get_policy("full"), "seeded")
        hits = [f for f in findings if f.check == "half-accum-contract"]
        assert len(hits) == 1
        f = hits[0]
        assert f.severity == ERROR
        assert f.site == "fno/layer2/spectral/contract"
        assert f.where == "seeded"
        assert "bfloat16" in f.detail

    def test_f32_accumulation_in_contract_scope_is_clean(self):
        def good(x, w):
            with jax.named_scope("fno/layer2/spectral/contract"):
                return jax.lax.dot_general(
                    x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), _DIMS,
                    preferred_element_type=jnp.float32)

        findings = trace_findings(
            good, (_sds(4, 8), _sds(8, 4)), get_policy("full"), "seeded")
        assert [f for f in findings if f.severity == ERROR] == []

    def test_half_accum_outside_contract_scope_is_only_a_warning(self):
        def dense(x, w):
            return jax.lax.dot_general(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), _DIMS)

        findings = trace_findings(
            dense, (_sds(4, 8), _sds(8, 4)), get_policy("full"), "seeded")
        hits = [f for f in findings if f.check == "half-accum"]
        assert len(hits) == 1 and hits[0].severity == WARNING

    def test_unstabilized_fp16_exp_flagged_and_tanh_clears_it(self):
        def risky(x):
            return jnp.exp(x.astype(jnp.float16))

        def stabilized(x):
            return jnp.exp(jnp.tanh(x.astype(jnp.float16)))

        policy = get_policy("full")
        flagged = trace_findings(risky, (_sds(8),), policy, "seeded")
        assert any(f.check == "fp16-overflow-risk" and f.severity == WARNING
                   for f in flagged)
        clean = trace_findings(stabilized, (_sds(8),), policy, "seeded")
        assert [f for f in clean if f.check == "fp16-overflow-risk"] == []

    def test_round_trip_cast_detected(self):
        def wasteful(x):
            return x.astype(jnp.float16).astype(jnp.float32) + 1.0

        findings = trace_findings(
            wasteful, (_sds(8),), get_policy("full"), "seeded")
        assert any(f.check == "round-trip-cast" for f in findings)

    def test_fp32_resident_demoted_site_is_an_error(self):
        """mixed_fno_fp16 demotes spectral storage to f16; a contract
        scope whose eqns never touch f16 contradicts the policy."""
        policy = get_policy("mixed_fno_fp16")
        site = "fno/layer0/spectral/contract"
        assert policy.at(site).spectral_dtype is not None  # test premise

        def all_f32(x, w):
            with jax.named_scope(site):
                return jax.lax.dot_general(
                    x, w, _DIMS, preferred_element_type=jnp.float32)

        findings = trace_findings(
            all_f32, (_sds(4, 8), _sds(8, 4)), policy, "seeded")
        hits = [f for f in findings if f.check == "fp32-resident"]
        assert len(hits) == 1
        assert hits[0].severity == ERROR and hits[0].site == site


class TestSeededRuleTableViolations:
    def test_shadowed_rule_detected(self):
        """The second entry sets only ``compute``, which the catch-all
        above it already supplies everywhere: dead under field-wise
        first-match resolution."""
        rules = (
            ("*", SiteRule(compute="float32")),
            ("fno/*", SiteRule(compute="bfloat16")),
        )
        dead = shadowed_entries(rules, site_universe())
        assert dead == [(1, "fno/*", ("compute",))]

        findings = rule_table_findings(tables={"seeded": rules})
        hits = [f for f in findings if f.check == "shadowed-rule"]
        assert len(hits) == 1
        f = hits[0]
        assert f.severity == ERROR
        assert f.site == "fno/*"
        assert f.where == "seeded[1]"

    def test_specific_before_catchall_is_not_shadowed(self):
        rules = (
            ("fno/*", SiteRule(compute="bfloat16")),
            ("*", SiteRule(compute="float32")),
        )
        assert shadowed_entries(rules, site_universe()) == []

    def test_distinct_field_is_not_shadowed(self):
        # the later entry contributes a field the catch-all leaves UNSET
        rules = (
            ("*", SiteRule(compute="float32")),
            ("fno/*", SiteRule(accum="float32")),
        )
        assert shadowed_entries(rules, site_universe()) == []

    def test_pattern_matching_nothing_is_an_error(self):
        rules = (("nonexistent/bogus/site", SiteRule(compute="float32")),)
        findings = rule_table_findings(tables={"seeded": rules})
        hits = [f for f in findings if f.check == "pattern-no-match"]
        assert len(hits) == 1 and hits[0].severity == ERROR


class TestSeededSiteLiteralViolations:
    def test_orphan_site_literal_detected(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent("""
            def f(policy, x):
                good = policy.at("fno/layer0/spectral/contract")
                bad = policy.at("fno/layer0/spectral/contracct")  # typo
                return good, bad
        """))
        findings = orphan_site_findings(str(tmp_path))
        assert len(findings) == 1
        f = findings[0]
        assert f.check == "orphan-site" and f.severity == ERROR
        assert f.site == "fno/layer0/spectral/contracct"
        assert f.where == "mod.py:4"

    def test_fstring_prefix_literals_recognised(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent("""
            def f(policy, i):
                return policy.at(f"sfno/layer{i}/spectral/fft_in")
        """))
        assert orphan_site_findings(str(tmp_path)) == []

    def test_syntax_error_fails_loudly(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        with pytest.raises(SyntaxError):
            orphan_site_findings(str(tmp_path))


class _FakeSpec:
    def __init__(self, block_shape, index_map):
        self.block_shape = block_shape
        self.index_map = index_map


def _plain_copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _disciplined_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += x_ref[...]


def _call(kernel, grid, in_specs, in_shapes, out_specs, out_shapes):
    return KernelCall(
        kernel=kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float16) for s in out_shapes],
        arg_shapes=[(s, jnp.dtype(jnp.float16)) for s in in_shapes],
    )


class TestSeededKernelViolations:
    def test_oob_index_map_detected(self):
        call = _call(
            _plain_copy_kernel, grid=(3,),
            in_specs=[_FakeSpec((8,), lambda i: (i,))], in_shapes=[(16,)],
            out_specs=[_FakeSpec((8,), lambda i: (0,))], out_shapes=[(8,)])
        findings = check_call(call, "seeded")
        oob = [f for f in findings if f.check == "index-oob"]
        assert oob and all(f.severity == ERROR for f in oob)
        assert "in[0]" in oob[0].detail

    def test_fused_fake_oob_index_map_detected(self):
        """A ``spectral_fused``-shaped call (batch-tiled grid over a
        4-rank operand) whose batch index map overruns the padded
        extent is caught at exactly the index-oob check — the real
        fused family's traced calls stay clean
        (``TestCleanTree::test_kernels_pass_clean``)."""
        call = _call(
            _plain_copy_kernel, grid=(2,),
            in_specs=[_FakeSpec((2, 4, 8, 8), lambda i: (i + 1, 0, 0, 0))],
            in_shapes=[(4, 4, 8, 8)],
            out_specs=[_FakeSpec((2, 4, 8, 8), lambda i: (i, 0, 0, 0))],
            out_shapes=[(4, 4, 8, 8)])
        findings = check_call(call, "seeded:spectral_fused")
        oob = [f for f in findings if f.check == "index-oob"]
        assert oob and all(f.severity == ERROR for f in oob)
        assert "in[0]" in oob[0].detail
        assert [f.check for f in findings if f.check != "index-oob"] == []

    def test_uncovered_output_block_detected(self):
        call = _call(
            _plain_copy_kernel, grid=(1,),
            in_specs=[_FakeSpec((8,), lambda i: (i,))], in_shapes=[(8,)],
            out_specs=[_FakeSpec((8,), lambda i: (0,))], out_shapes=[(16,)])
        findings = check_call(call, "seeded")
        assert any(f.check == "output-not-covered" and f.severity == ERROR
                   for f in findings)

    def test_revisited_block_without_discipline_detected(self):
        call = _call(
            _plain_copy_kernel, grid=(2,),
            in_specs=[_FakeSpec((8,), lambda i: (i,))], in_shapes=[(16,)],
            out_specs=[_FakeSpec((8,), lambda i: (0,))], out_shapes=[(8,)])
        findings = check_call(call, "seeded")
        assert any(f.check == "accum-discipline" for f in findings)

    def test_init_accumulate_pattern_passes(self):
        call = _call(
            _disciplined_kernel, grid=(2,),
            in_specs=[_FakeSpec((8,), lambda i: (i,))], in_shapes=[(16,)],
            out_specs=[_FakeSpec((8,), lambda i: (0,))], out_shapes=[(8,)])
        assert check_call(call, "seeded") == []

    def test_tile_bytes_counts_both_sides(self):
        call = _call(
            _plain_copy_kernel, grid=(1,),
            in_specs=[_FakeSpec((8,), lambda i: (i,))], in_shapes=[(8,)],
            out_specs=[_FakeSpec((8,), lambda i: (0,))], out_shapes=[(8,)])
        assert tile_bytes(call) == 8 * 2 + 8 * 2  # f16 in + out tiles


# ---------------------------------------------------------------------------
# Clean tree: the shipped repo produces no error-severity findings
# ---------------------------------------------------------------------------


class TestCleanTree:
    def test_sites_pass_clean_on_repo(self):
        findings = sites_pass(_REPO_SRC)
        assert [f for f in findings if f.severity == ERROR] == []

    def test_kernels_pass_clean(self):
        findings = kernels_pass()
        assert [f for f in findings if f.severity == ERROR] == []

    def test_kernels_pass_covers_fused_family(self):
        from repro.analyze.kernels import kernel_families

        names = [name for name, _, _ in kernel_families()]
        assert "spectral_fused/fwd" in names
        assert "spectral_fused/bwd" in names

    @pytest.mark.parametrize("policy_name", ["full", "mixed_fno_fp16"])
    def test_model_forward_has_no_errors(self, policy_name):
        from repro.analyze import model_findings

        findings = model_findings("fno", get_policy(policy_name),
                                  use_pallas=True)
        assert [f for f in findings if f.severity == ERROR] == []

    @pytest.mark.parametrize("on_tpu", [False, True], ids=["fft", "dft"])
    def test_sfno_findings_need_no_new_suppression(self, monkeypatch,
                                                   on_tpu):
        """The SFNO under the paper's policy, on either longitude path of
        its spherical transform: no error, and nothing suppressed beyond
        the boundary quantisation's reviewed round trips."""
        from repro.analyze import model_findings
        from repro.core import spectral

        monkeypatch.setattr(spectral, "_on_tpu", lambda: on_tpu)
        findings = model_findings("sfno", get_policy("mixed_fno_bf16"),
                                  use_pallas=True)
        active, suppressed = partition(
            findings, load_suppressions(os.path.join(
                os.path.dirname(_REPO_SRC), "analyze.toml")))
        assert [f for f in active if f.severity == ERROR] == []
        assert {f.check for f in suppressed} == {"round-trip-cast"}


# ---------------------------------------------------------------------------
# Suppression machinery
# ---------------------------------------------------------------------------


def _finding(check="half-accum", severity=WARNING, site="fno/dense",
             where="fno/full"):
    return Finding(pass_name="dataflow", check=check, severity=severity,
                   site=site, where=where, detail="d")


class TestSuppressions:
    def test_partition_by_check_and_site_pattern(self):
        sup = Suppression(check="half-accum", reason="reviewed",
                          site="fno/*")
        active, suppressed = partition(
            [_finding(), _finding(site="sfno/dense"),
             _finding(check="round-trip-cast")],
            [sup])
        assert len(suppressed) == 1 and suppressed[0].site == "fno/dense"
        assert len(active) == 2

    def test_site_pattern_never_matches_siteless_finding(self):
        sup = Suppression(check="half-accum", reason="r", site="*")
        active, suppressed = partition([_finding(site=None)], [sup])
        assert suppressed == [] and len(active) == 1

    def test_load_suppressions_roundtrip(self, tmp_path):
        p = tmp_path / "analyze.toml"
        p.write_text(textwrap.dedent("""
            # comment
            [[suppress]]
            check = "round-trip-cast"
            site = "*/spectral/fft_in"
            reason = "Thm 3.2 boundary quantiser"
        """))
        sups = load_suppressions(str(p))
        assert sups == (Suppression(
            check="round-trip-cast", reason="Thm 3.2 boundary quantiser",
            site="*/spectral/fft_in"),)

    def test_missing_file_is_empty_allowlist(self, tmp_path):
        assert load_suppressions(str(tmp_path / "nope.toml")) == ()

    def test_entry_without_reason_rejected(self, tmp_path):
        p = tmp_path / "analyze.toml"
        p.write_text('[[suppress]]\ncheck = "half-accum"\n')
        with pytest.raises(ValueError, match="reason"):
            load_suppressions(str(p))

    def test_unknown_key_rejected(self, tmp_path):
        p = tmp_path / "analyze.toml"
        p.write_text(
            '[[suppress]]\ncheck = "x"\nreason = "r"\nseverty = "oops"\n')
        with pytest.raises(ValueError, match="unknown"):
            load_suppressions(str(p))

    def test_shipped_suppression_file_parses(self):
        path = os.path.join(_REPO_SRC, "..", "analyze.toml")
        sups = load_suppressions(path)
        assert sups, "repo analyze.toml should ship reviewed entries"
        assert all(s.reason for s in sups)

    def test_dedupe_keeps_first_seen_order(self):
        a, b = _finding(), _finding(check="other")
        assert dedupe([a, b, a]) == [a, b]


# ---------------------------------------------------------------------------
# CLI end-to-end (cheap configuration)
# ---------------------------------------------------------------------------


class TestCLI:
    def test_main_writes_report_and_exits_zero(self, tmp_path, capsys):
        from repro.analyze.__main__ import main

        out = tmp_path / "analyze.json"
        rc = main([
            "--policies", "full", "--models", "fno", "--pallas", "off",
            "--no-trainer", "--skip", "kernels", "sites",
            "--out", str(out),
        ])
        assert rc == 0
        assert out.exists()
        import json

        report = json.loads(out.read_text())
        assert report["policies"] == ["full"]
        assert report["summary"]["errors"] == 0
        assert "wrote" in capsys.readouterr().out

"""repro.tune: the autotuner's cache, oracle gate, and its reach into
kernel tile resolution.

The load-bearing claims:

  * robustness — corrupted / stale / version-mismatched calibration
    files degrade to a warning plus the static heuristic, never a
    crash, and stale entries are invisible at lookup;
  * the oracle gate is live — ``validate --perturb 2`` (a seeded
    Thm 3.2 budget violation) must reject every entry;
  * resolution really consults the cache — a seeded entry provably
    changes the executed Pallas grid on BOTH the forward and backward
    kernels vs the heuristic tiling, and the source counters say so.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analyze.kernels import calibration_pass, record_pallas_calls
from repro.core import get_policy
from repro.kernels import ops
from repro.kernels.spectral_contract import (
    KERNEL_VERSION,
    VMEM_BUDGET,
    pick_block_m,
)
from repro.tune import cache as cache_mod
from repro.tune import oracle, space
from repro.tune.__main__ import main as tune_main

jax.config.update("jax_platform_name", "cpu")

BACKEND = jax.default_backend()


@pytest.fixture(autouse=True)
def _pristine_calibration(monkeypatch):
    """No test leaks activation state or the env var into another."""
    monkeypatch.delenv(cache_mod.ENV_VAR, raising=False)
    cache_mod.activate(None)
    yield
    cache_mod.activate(None)


# shared with test_kernels*.py via tests/helpers.py
from helpers import (  # noqa: E402
    calibration_entry as _entry,
    calibration_state as _state_with,
)


# ---------------------------------------------------------------------------
# search space
# ---------------------------------------------------------------------------

class TestSearchSpace:
    @pytest.mark.parametrize("family,shape", [
        ("dense", (2, 8, 8, 40)),
        ("dense-fused", (2, 8, 8, 40)),
        ("cp", (2, 8, 8, 4, 40)),
        ("lshared", (2, 8, 8, 12, 9)),
    ])
    def test_candidates_legal(self, family, shape):
        cands = space.candidates(family, shape, "bfloat16")
        assert cands, f"no candidates for {family} {shape}"
        itemsize = space.family_itemsize(family, "bfloat16")
        for c in cands:
            for block, direction in ((c.block_fwd, "fwd"),
                                     (c.block_bwd, "bwd")):
                assert block & (block - 1) == 0
                assert space.tile_vmem_bytes(
                    family, shape, block, itemsize, direction
                ) <= space.DEFAULT_BUDGET

    def test_fused_prices_at_f32(self):
        """The fused family streams f32 operand tiles, so its legal
        blocks can only shrink relative to plain dense."""
        shape = (4, 32, 32, 512)
        dense = space.legal_blocks("dense", shape, "bfloat16", "fwd")
        fused = space.legal_blocks("dense-fused", shape, "bfloat16", "fwd")
        assert max(fused) <= max(dense)

    def test_limit_caps_cross_product(self):
        cands = space.candidates("dense", (2, 8, 8, 40), "bfloat16", limit=3)
        assert len(cands) == 3


# ---------------------------------------------------------------------------
# cache: robustness of every failure mode
# ---------------------------------------------------------------------------

class TestCacheRobustness:
    def test_corrupt_json_warns_and_falls_back(self, tmp_path):
        bad = tmp_path / "corrupt.json"
        bad.write_text("{ this is not json")
        with pytest.raises(cache_mod.CalibrationError):
            cache_mod.load(bad)
        with pytest.warns(UserWarning, match="calibration"):
            assert cache_mod.safe_load(bad) is None
        with pytest.warns(UserWarning):
            cache_mod.activate(str(bad))
        assert cache_mod.active_cache() is None

    def test_missing_file_and_bad_structure(self, tmp_path):
        with pytest.raises(cache_mod.CalibrationError, match="not found"):
            cache_mod.load(tmp_path / "absent.json")
        noent = tmp_path / "noentries.json"
        noent.write_text(json.dumps({"format_version": 1}))
        with pytest.raises(cache_mod.CalibrationError, match="entries"):
            cache_mod.load(noent)

    def test_format_version_mismatch_rejected(self, tmp_path):
        p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40)))
        raw = json.loads(open(p).read())
        raw["format_version"] = 999
        open(p, "w").write(json.dumps(raw))
        with pytest.raises(cache_mod.CalibrationError, match="format_version"):
            cache_mod.load(p)

    def test_kernel_version_bump_invalidates_entry(self, tmp_path):
        p = _state_with(tmp_path, _entry(
            "dense", (2, 8, 8, 40), kernel_version=KERNEL_VERSION - 1))
        state = cache_mod.load(p)
        assert state.lookup("dense", (2, 8, 8, 40), "bfloat16") is None
        assert state.counters["stale"] == 1

    def test_backend_mismatch_invalidates_entry(self, tmp_path):
        p = _state_with(tmp_path, _entry(
            "dense", (2, 8, 8, 40), backend="not-a-backend"))
        state = cache_mod.load(p)
        assert state.lookup("dense", (2, 8, 8, 40), "bfloat16") is None
        assert state.counters["stale"] == 1

    @pytest.mark.parametrize("defect", [
        {"validated": False},
        {"block_fwd": 7},          # not a power of two
        {"block_bwd": "8"},        # wrong type
    ])
    def test_defective_entries_are_invisible(self, tmp_path, defect):
        p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40), **defect))
        state = cache_mod.load(p)
        assert state.lookup("dense", (2, 8, 8, 40), "bfloat16") is None

    def test_hit_and_miss_counters(self, tmp_path):
        p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40)))
        state = cache_mod.load(p)
        assert state.lookup("dense", (2, 8, 8, 40), "bfloat16") is not None
        assert state.lookup("dense", (9, 9, 9, 9), "bfloat16") is None
        assert state.counters == {"hits": 1, "misses": 1, "stale": 0}

    def test_atomic_save_roundtrip(self, tmp_path):
        p = _state_with(tmp_path, _entry("cp", (2, 8, 8, 4, 40)))
        state = cache_mod.load(p)
        assert state.path == str(p)
        assert state.lookup("cp", (2, 8, 8, 4, 40), "bfloat16") is not None
        # no tempfile droppings from the atomic write
        assert [f.name for f in tmp_path.iterdir()] == ["state.json"]

    def test_env_var_resolution_tracks_mtime(self, tmp_path, monkeypatch):
        p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40), block_fwd=8))
        monkeypatch.setenv(cache_mod.ENV_VAR, str(p))
        c1 = cache_mod.active_cache()
        assert c1.lookup("dense", (2, 8, 8, 40), "bfloat16")["block_fwd"] == 8
        _state_with(tmp_path, _entry("dense", (2, 8, 8, 40), block_fwd=16))
        os.utime(p, ns=(0, 0))  # force a visible mtime change
        c2 = cache_mod.active_cache()
        assert c2.lookup("dense", (2, 8, 8, 40), "bfloat16")["block_fwd"] == 16

    def test_explicit_activation_beats_env(self, tmp_path, monkeypatch):
        p_env = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40)),
                            name="env.json")
        monkeypatch.setenv(cache_mod.ENV_VAR, str(p_env))
        explicit = cache_mod.CalibrationCache(entries={}, backend=BACKEND)
        cache_mod.activate(explicit)
        assert cache_mod.active_cache() is explicit
        cache_mod.activate(None)
        assert cache_mod.active_cache().path == str(p_env)

    def test_bad_file_never_crashes_resolution(self, tmp_path, monkeypatch):
        """The acceptance bar: a corrupt state behind the env var costs a
        warning, and the kernel wrapper still runs on the heuristic."""
        bad = tmp_path / "bad.json"
        bad.write_text("]]garbage")
        monkeypatch.setenv(cache_mod.ENV_VAR, str(bad))
        site = get_policy("mixed_fno_bf16").at("fno/layer0/spectral/contract")
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 3, 4, 5) + 1j * rng.randn(2, 3, 4, 5),
                        jnp.complex64)
        w = jnp.asarray(rng.randn(3, 4, 4, 5) + 1j * rng.randn(3, 4, 4, 5),
                        jnp.complex64)
        with pytest.warns(UserWarning, match="calibration"):
            y = ops.spectral_contract(x, w, policy=site)
        assert y.shape == (2, 4, 4, 5)
        stats = ops.tile_resolution_stats()
        assert stats["calibration_state"] is None
        assert stats["sources"]["heuristic"] >= 1


# ---------------------------------------------------------------------------
# the seeded entry flips the executed tiling (acceptance criterion)
# ---------------------------------------------------------------------------

def _grids_of_step(x, w, site):
    """Executed Pallas grids for one value_and_grad through the dense
    wrapper: [fwd, fwd(recompute), bwd_dx, bwd_dw]."""
    def loss(x, w):
        y = ops.spectral_contract(x, w, policy=site)
        return jnp.sum(jnp.abs(y) ** 2)

    with record_pallas_calls() as calls:
        jax.block_until_ready(jax.value_and_grad(loss, argnums=(0, 1))(x, w))
    return [c.grid for c in calls]


@pytest.mark.parametrize("fused", [False, True],
                         ids=["dense", "dense-fused"])
def test_seeded_entry_flips_executed_tiling(tmp_path, monkeypatch, fused):
    B, I, O, M = 2, 8, 8, 40
    if not fused:
        # the plain dense family: operand tiles too large for the f32
        # cast in the tile prologue
        monkeypatch.setattr(ops, "_fused_cast_fits", lambda *a: False)
    site = get_policy("mixed_fno_bf16").at("fno/layer0/spectral/contract")
    rng = np.random.RandomState(7)
    x = jnp.asarray(0.5 * (rng.randn(B, I, M) + 1j * rng.randn(B, I, M)),
                    jnp.complex64)
    w = jnp.asarray(0.5 * (rng.randn(I, O, M) + 1j * rng.randn(I, O, M)),
                    jnp.complex64)

    family = "dense-fused" if fused else "dense"
    itemsize = 4 if fused else 2
    heur = pick_block_m(B, I, O, M, itemsize=itemsize)
    seeded_fwd, seeded_bwd = 8, 16
    assert heur not in (seeded_fwd, seeded_bwd), "seed must differ"

    grids_heur = _grids_of_step(x, w, site)

    p = _state_with(tmp_path, _entry(family, (B, I, O, M),
                                     block_fwd=seeded_fwd,
                                     block_bwd=seeded_bwd))
    cache_mod.activate(str(p))
    before = dict(ops._TILE_SOURCES)
    grids_cal = _grids_of_step(x, w, site)

    # fwd kernels run on the seeded fwd tile, bwd kernels on the seeded
    # bwd tile — and every grid differs from the heuristic run's
    steps = lambda blk: (-(-M // blk),)  # noqa: E731
    assert grids_cal[0] == steps(seeded_fwd)
    assert grids_cal[-1] == steps(seeded_bwd)
    assert grids_cal != grids_heur
    assert grids_heur[0] == steps(heur)

    stats = ops.tile_resolution_stats()
    assert stats["calibration_state"] == str(p)
    assert stats["sources"]["calibrated"] > before["calibrated"]
    assert stats["cache"]["hits"] >= 1


def test_trainer_and_engine_activate_state(tmp_path):
    from repro.train import Trainer, TrainerConfig

    p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40)))
    cfg = TrainerConfig(total_steps=1, calibration_state=str(p))
    Trainer(lambda prm, b, pol: jnp.sum(prm["w"] ** 2),
            {"w": jnp.ones((2,))}, cfg)
    assert cache_mod.active_cache().path == str(p)

    cache_mod.activate(None)
    from repro.models import FNOConfig, init_fno
    from repro.serve.operator import OperatorEngine

    mcfg = FNOConfig(in_channels=1, out_channels=1, hidden_channels=4,
                     lifting_channels=4, projection_channels=4,
                     n_layers=1, modes=(3, 3))
    params = init_fno(jax.random.PRNGKey(0), mcfg)
    eng = OperatorEngine(params, mcfg, calibration_state=str(p))
    stats = eng.stats()
    assert stats["tiles"]["calibration_state"] == str(p)
    assert set(stats["tiles"]["sources"]) == {"heuristic", "calibrated"}


# ---------------------------------------------------------------------------
# oracle gate + CLI
# ---------------------------------------------------------------------------

class TestOracleGate:
    def test_correct_candidate_passes(self):
        cand = space.Candidate("dense", (2, 4, 4, 9), "bfloat16", 8, 8)
        verdict = oracle.check(cand, interpret=True)
        assert verdict["passed"], verdict

    def test_seeded_violation_is_rejected(self):
        cand = space.Candidate("dense", (2, 4, 4, 9), "bfloat16", 8, 8)
        verdict = oracle.check(cand, interpret=True, perturb=2.0)
        assert not verdict["passed"]
        assert verdict["worst_excess"] > 0

    def test_validate_cli_rejects_seeded_violation(self, tmp_path, capsys):
        p = _state_with(
            tmp_path,
            _entry("dense", (2, 4, 4, 9)))
        argv = ["validate", "--state", str(p), "--interpret"]
        assert tune_main(argv) == 0
        assert tune_main(argv + ["--perturb", "2"]) == 1
        assert "REJECT" in capsys.readouterr().out

    def test_validate_cli_skips_stale_and_prunes_corrupt(self, tmp_path):
        p = _state_with(
            tmp_path,
            _entry("dense", (2, 4, 4, 9)),
            _entry("dense", (2, 4, 4, 11),
                   kernel_version=KERNEL_VERSION - 1),
            _entry("dense", (2, 4, 4, 13), block_fwd=7),
        )
        # stale entry is skipped (not a failure); corrupt one fails
        assert tune_main(["validate", "--state", str(p),
                          "--interpret", "--prune"]) == 1
        state = cache_mod.load(p)
        assert cache_mod.entry_key(
            "dense", (2, 4, 4, 13), "bfloat16") not in state.entries
        assert cache_mod.entry_key(
            "dense", (2, 4, 4, 11), "bfloat16") in state.entries
        assert tune_main(["validate", "--state", str(p),
                          "--interpret"]) == 0

    def test_validate_cli_unreadable_state(self, tmp_path):
        assert tune_main(["validate", "--state",
                          str(tmp_path / "nope.json")]) == 2


def test_tune_smoke_cycle(tmp_path):
    """The CI loop end-to-end: tune --smoke admits oracle-validated
    entries, validate re-checks them, report renders."""
    p = tmp_path / "cal.json"
    rc = tune_main(["tune", "--smoke", "--interpret", "--state", str(p),
                    "--limit", "1", "--iters", "1"])
    assert rc == 0
    state = cache_mod.load(p)
    assert state.entries, "tune wrote no entries"
    for ent in state.entries.values():
        assert ent["validated"] and ent["interpret"]
        assert ent["kernel_version"] == KERNEL_VERSION
        # an interpreted run has no device peak to take a share of
        assert ent["gbps"] >= 0 and ent["roofline_fraction"] is None
    assert tune_main(["validate", "--state", str(p), "--interpret"]) == 0
    assert tune_main(["report", "--state", str(p)]) == 0


# ---------------------------------------------------------------------------
# analyze: calibration-coverage
# ---------------------------------------------------------------------------

class TestCalibrationCoverage:
    def test_clean_state_no_findings(self, tmp_path):
        p = _state_with(tmp_path, _entry("dense", (2, 8, 8, 40)))
        assert calibration_pass(str(p)) == []

    def test_oversized_tile_is_an_error(self, tmp_path):
        # a "tuned" tile whose bwd working set overflows VMEM: the
        # coverage check must flag it even though it is structurally fine
        p = _state_with(tmp_path, _entry(
            "dense", (64, 512, 512, 4096), block_fwd=8, block_bwd=4096))
        findings = calibration_pass(str(p))
        assert findings and all(f.check == "calibration-coverage"
                                for f in findings)
        assert any("budget" in f.detail or "VMEM" in f.detail
                   for f in findings)

    def test_unreadable_state_is_an_error_finding(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("nope")
        findings = calibration_pass(str(bad))
        assert len(findings) == 1 and findings[0].severity == "error"

    def test_no_path_no_findings(self, monkeypatch):
        monkeypatch.delenv(cache_mod.ENV_VAR, raising=False)
        assert calibration_pass(None) == []


def test_oversized_entry_never_served(tmp_path):
    """Defense in depth: lookup itself doesn't re-price VMEM (that's the
    analyze check), but the seeded oversized entry still routes through
    the kernels' padding path without crashing."""
    B, I, O, M = 2, 4, 4, 9
    p = _state_with(tmp_path, _entry("dense-fused", (B, I, O, M),
                                     block_fwd=16, block_bwd=16))
    cache_mod.activate(str(p))
    site = get_policy("mixed_fno_bf16").at("fno/layer0/spectral/contract")
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(B, I, 3, 3) + 1j * rng.randn(B, I, 3, 3),
                    jnp.complex64)
    w = jnp.asarray(rng.randn(I, O, 3, 3) + 1j * rng.randn(I, O, 3, 3),
                    jnp.complex64)
    y = ops.spectral_contract(x, w, policy=site)
    assert y.shape == (B, O, 3, 3)
    assert VMEM_BUDGET > 0  # the constant the coverage check prices against

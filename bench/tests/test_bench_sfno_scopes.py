"""The SFNO's spherical transforms carry the FFT scopes that
``harness.program_trace`` reads.

A small compiled SFNO training step, read by ``scopes_from_hlo`` as the
benchmark reads the chip's step: the forward SHT sits under
``sfno/layer0/spectral/fft_in`` and the inverse under ``.../fft_out``,
both matched by ``program_trace.FFT`` as the FNO's transforms are, and
the backward of the forward transform keeps its scope inside
``transpose(...)``.
"""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import program_trace as pt  # noqa: E402


def test_sfno_step_holds_both_transform_scopes():
    import jax
    import jax.numpy as jnp

    from repro.models import SFNOConfig, init_sfno, sfno_apply
    from repro.train import Trainer, TrainerConfig, relative_l2

    cfg = SFNOConfig(in_channels=1, out_channels=1, hidden_channels=4,
                     n_layers=2, nlat=8, nlon=16, lmax=4, mmax=4,
                     lifting_channels=4, projection_channels=4,
                     use_pallas=False)
    tr = Trainer(lambda p, b, pol: relative_l2(
        sfno_apply(p, b["x"], cfg, pol), b["t"]),
        init_sfno(jax.random.PRNGKey(0), cfg), TrainerConfig(total_steps=1))
    batch = {"x": jnp.ones((2, 1, 8, 16)), "t": jnp.ones((2, 1, 8, 16))}
    scopes = set(pt.scopes_from_hlo(tr.lower(batch).compile().as_text())
                 .values())
    for half in ("fft_in", "fft_out"):
        site = f"sfno/layer0/spectral/{half}"
        assert any(site in s and pt.FFT.search(s) for s in scopes), site
    assert any("transpose(" in s and "sfno/layer0/spectral/fft_in" in s
               for s in scopes)

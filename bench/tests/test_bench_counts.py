"""Operation and byte counts of the yardstick against hand counts at small
shapes: each kernel family, each configuration's model FLOPs, and the
census of kernel calls a jitted step makes."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import kernels, registry  # noqa: E402
from harness.kernels import Aval  # noqa: E402


def av(shape, dtype="float32"):
    return Aval(tuple(shape), dtype, 2 if dtype == "bfloat16" else 4)


def pair(shape, dtype="float32"):
    return [av(shape, dtype)] * 2


FAMS = kernels.families()

# (family, kernel name, operands, results, FLOPs, bytes), counted by hand
CASES = [
    # B=2, I=3, O=5, M=128: 8*B*I*O*M = 30720 for each direction
    ("dense", "_dense_fwd_kernel",
     pair((2, 3, 128)) + pair((3, 5, 128)), pair((2, 5, 128), "bfloat16"),
     30720, 2 * 2 * 3 * 128 * 4 + 2 * 3 * 5 * 128 * 4 + 2 * 2 * 5 * 128 * 2),
    ("dense", "_dense_bwd_x_kernel",
     pair((2, 5, 128)) + pair((3, 5, 128)), pair((2, 3, 128)),
     30720, 8 * (2 * 5 * 128 + 3 * 5 * 128 + 2 * 3 * 128)),
    ("dense", "_dense_bwd_w_kernel",
     pair((2, 3, 128)) + pair((2, 5, 128)), pair((3, 5, 128)),
     30720, 8 * (2 * 3 * 128 + 2 * 5 * 128 + 3 * 5 * 128)),
    # B=2, I=3, O=5, L=4, M=6: 8*B*I*O*L*M = 5760
    ("lshared", "_lshared_fwd_kernel",
     pair((2, 3, 4, 6)) + pair((3, 5, 4)), pair((2, 5, 4, 6)),
     5760, 4 * 2 * (144 + 60 + 240)),
    ("lshared", "_lshared_bwd_w_kernel",
     pair((2, 3, 4, 6)) + pair((2, 5, 4, 6)), pair((3, 5, 4)),
     5760, 4 * 2 * (144 + 240 + 60)),
    # B=2, I=3, O=5, R=4, M=6: 8*48*(3+5) + 6*48; backward 16*48*8 + 14*48
    ("cp", "_cp_fwd_kernel",
     pair((2, 3, 6)) + pair((3, 4)) + pair((5, 4)) + pair((4, 6)),
     pair((2, 5, 6)), 3360, 4 * 2 * (36 + 12 + 20 + 24 + 60)),
    ("cp", "_cp_bwd_kernel",
     pair((2, 3, 6)) + pair((3, 4)) + pair((5, 4)) + pair((4, 6))
     + pair((2, 5, 6)),
     pair((2, 3, 6)) + pair((3, 4)) + pair((5, 4)) + pair((4, 6)),
     6816, 4 * 2 * (36 + 12 + 20 + 24 + 60 + 36 + 12 + 20 + 24)),
    # B=1, I=2, O=3, spatial 4x8, retained rows 2x3 (Mh = 6): forward DFT
    # 4*64*2 + 8*32*3, contraction 8*1*2*3*6, inverse 8*18*4 + 4*36*8
    ("fused", "_fused_fwd_kernel",
     [av((1, 2, 4, 8)), av((2, 3, 6)), av((2, 3, 6))]
     + pair((2, 4)) + pair((3, 8)) + pair((2, 4)) + pair((3, 8)),
     [av((1, 3, 4, 8))], 3296,
     4 * (64 + 2 * 36 + 2 * (8 + 24) * 2 + 96)),
]


@pytest.mark.parametrize("fam,name,ins,outs,flops,nbytes", CASES,
                         ids=[c[1] for c in CASES])
def test_kernel_family_counts(fam, name, ins, outs, flops, nbytes):
    mod = FAMS[fam]
    assert name.startswith(mod.PREFIX)
    assert mod.flops(name, ins, outs) == pytest.approx(flops, rel=1e-12)
    assert mod.hbm_bytes(name, ins, outs) == nbytes


def test_least_time_takes_the_binding_roof():
    calls = [kernels.Call(c[1], c[2], c[3], 2) for c in CASES[:1]]
    peaks = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e9}
    got = kernels.least_seconds(calls, peaks, FAMS)
    assert got["bound"] == "compute"
    assert got["seconds"] == pytest.approx(2 * 30720 / 1e6)


def _fno_small():
    return {"in_channels": 1, "out_channels": 1, "hidden_channels": 4,
            "lifting_channels": 8, "projection_channels": 8, "n_layers": 2,
            "modes": [2, 2], "grid": [8, 8]}


def test_fno_model_flops_hand_count():
    fno = registry.load_module("models", "fno")
    # lifting/projection 2*64*(3*8 + 8*4 + 4*8 + 8); per layer skip
    # 2*64*16, contraction 8*16*8, two FFTs of 4 channels 2*4*2.5*64*6
    assert fno.forward_flops(_fno_small()) == pytest.approx(
        12288 + 2 * (2048 + 1024 + 7680))


def test_sfno_model_flops_hand_count():
    sfno = registry.load_module("models", "sfno")
    cfg = {"in_channels": 3, "out_channels": 3, "hidden_channels": 4,
           "lifting_channels": 8, "projection_channels": 8, "n_layers": 1,
           "nlat": 4, "nlon": 8, "lmax": 3, "mmax": 2}
    dense = 2 * 32 * (3 * 8 + 8 * 4 + 4 * 8 + 8 * 3)
    layer = (2 * 32 * 16 + 2 * 4 * 4 * 2 * 3 * 4
             + 2 * 4 * 4 * 2.5 * 8 * 3 + 8 * 16 * 3 * 2)
    assert sfno.forward_flops(cfg) == pytest.approx(dense + layer)


@pytest.mark.parametrize("name,gflop", [("fno_darcy", 2.206203904),
                                        ("sfno_swe", None)])
def test_configuration_flops(name, gflop):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fam = registry.load_module("models", cfg["family"])
    f = fam.forward_flops(cfg)
    if gflop is not None:
        assert f == pytest.approx(gflop * 1e9, rel=1e-9)
    assert f > 0


def test_census_of_a_jitted_step():
    """A two-layer FNO gradient on the dense Pallas path: per layer and
    corner one forward and two backward kernels, the layers under one
    scan, so 2 layers x 2 corners x 3 = 12 calls per step."""
    import jax
    import jax.numpy as jnp

    from repro.configs.fno_paper import FNO_DARCY_SMOKE
    from repro.core import get_policy
    from repro.models import fno_apply, init_fno

    cfg = dataclasses.replace(FNO_DARCY_SMOKE, use_pallas=True,
                              fuse_spectral=False)
    pol = get_policy("mixed_fno_bf16")
    params = jax.eval_shape(lambda: init_fno(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, 1, 16, 16), jnp.float32)

    def loss(p, v):
        return jnp.sum(fno_apply(p, v, cfg, pol) ** 2)

    calls = kernels.step_calls(jax.jit(jax.grad(loss)), params, x)
    by_name = {}
    for c in calls:
        by_name[c.name] = by_name.get(c.name, 0) + c.count
    assert by_name == {"_dense_fwd_kernel": 4, "_dense_bwd_x_kernel": 4,
                       "_dense_bwd_w_kernel": 4}
    priced = kernels.price(calls, FAMS)
    assert priced["unpriced"] == []
    assert priced["by_family"]["dense"]["calls"] == 12

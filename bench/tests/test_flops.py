"""bench/flops.py and the configuration's operation count, on shapes
counted by hand."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.harness import load_module

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_ssd_scan_cost_by_hand():
    # b=1, s=4, h=2, p=3, n=5, chunk 2: two chunks of two steps
    # per chunk: C·Bᵀ 2*2*5*2 = 40; per head 2*2*2*3 + 2*2*5*3 + 2*5*2*3 = 144
    f, nbytes = flops.ssd_scan_cost(1, 4, 2, 3, 5, 2)
    assert f == 2 * (40 + 2 * 144)
    # x in bf16 and y in f32: 24 values * 6 bytes; dt 8 * 4; B and C 2*20*2;
    # A 2 * 4
    assert nbytes == 144 + 32 + 80 + 8


def test_ssd_flops_per_token():
    assert flops.ssd_flops_per_token(2, 3, 5, 2) == (40 + 2 * 144) / 2


def test_roofline_picks_the_larger_bound():
    assert flops.roofline_s(10.0, 1.0, 10.0, 10.0) == (1.0, "compute")
    assert flops.roofline_s(1.0, 10.0, 10.0, 10.0) == (1.0, "memory")


def test_mamba2_train_flops_by_hand():
    ref = load_module(CONFIGS / "mamba2_130m.py", "ref_m2")
    d = {"d_model": 4, "n_layers": 2, "vocab": 10, "ssm_state": 5,
         "ssm_expand": 2, "ssm_head_dim": 4}
    # DI 8, H 2: in_proj 4*(16+10+2) = 112, out_proj 8*4 = 32, head 10*4
    ssd = flops.ssd_flops_per_token(2, 4, 5, 2)
    fwd = 2 * (2 * 144 + 40) + 2 * ssd
    assert ref.train_flops_per_token(d, 2) == pytest.approx(3 * fwd)


def test_mamba2_published_count():
    """859.6 MFLOP a token at the published sizes and chunk 128."""
    ref = load_module(CONFIGS / "mamba2_130m.py", "ref_m2")
    conf = json.loads((CONFIGS / "mamba2_130m.json").read_text())
    f = ref.train_flops_per_token(ref.dims(conf), 128)
    assert f == pytest.approx(859.6e6, rel=1e-3)

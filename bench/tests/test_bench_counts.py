"""The yardstick's counts against hand counts, and the peaks table."""
import json

import pytest

from bench import counts
from bench.tests.harness import ROOT, TRAIN_CONFIG

MAMBA2_780M_L4 = json.loads(
    (ROOT / "bench" / "configs" / "mamba2-780m.l4.n4.chip1.json").read_text())


def test_smoke_matmul_params_by_hand():
    # d=128, inner=256, H=8, P=32, N=16: in_proj 128*(256 + 288 + 8),
    # out_proj 256*128, two layers, tied head 128*512
    per_layer = 128 * (256 + 288 + 8) + 256 * 128
    assert per_layer == 103_424
    assert counts.mamba2_matmul_params(TRAIN_CONFIG) == \
        2 * per_layer + 128 * 512 == 272_384


def test_smoke_ssd_flops_by_hand():
    # per token per layer, Q=32: C B^T 32*16, diag 8*32*32, state and
    # off-diagonal 2*8*16*32 multiply-adds
    macs = 32 * 16 + 8 * 32 * 32 + 2 * 8 * 16 * 32
    assert macs == 16_896
    assert counts.ssd_forward_flops_per_token(TRAIN_CONFIG) == 2 * macs


def test_smoke_train_flops_per_token_by_hand():
    fwd = 2 * 272_384 + 2 * 33_792
    assert counts.mamba2_train_flops_per_token(TRAIN_CONFIG) == 3 * fwd \
        == 1_837_056


def test_780m_flops_per_token_by_hand():
    # 4 layers of 1536*(3072+3328+48) + 3072*1536, head 1536*50280
    matmul = 4 * (1536 * 6448 + 3072 * 1536) + 1536 * 50280
    assert matmul == 135_720_960
    ssd = 2 * (256 * 128 + 48 * (256 * 64 + 2 * 128 * 64))
    assert counts.mamba2_train_flops_per_token(MAMBA2_780M_L4) == \
        3 * (2 * matmul + 4 * ssd) == 852_860_928


@pytest.mark.parametrize("variant,calls", [("dasha", 1), ("mvr", 2)])
def test_step_flops_count_oracle_calls(variant, calls):
    per_token = counts.mamba2_train_flops_per_token(MAMBA2_780M_L4)
    assert counts.train_step_flops(MAMBA2_780M_L4, variant, 8192) == \
        per_token * 8192 * calls


def test_smoke_params_by_hand():
    # per layer: ln 128, in_proj 128*552, conv 4*288 + 288, dt_bias/A/D
    # 3*8, gated norm 256, out_proj 256*128; embed 512*128, final norm 128
    per_layer = 128 + 128 * 552 + 4 * 288 + 288 + 24 + 256 + 256 * 128
    assert counts.mamba2_params(TRAIN_CONFIG) == \
        2 * per_layer + 512 * 128 + 128


@pytest.mark.parametrize("variant,per_elem", [("dasha", 16), ("mvr", 24)])
def test_update_bytes_are_the_algorithms(variant, per_elem):
    # DASHA: read grad, h_i, g_i and write g_i (f32); MVR also reads the
    # old gradient and writes h_i; the mask and h_i <- grad cost nothing
    n = counts.mamba2_params(MAMBA2_780M_L4)
    assert counts.node_update_min_bytes(MAMBA2_780M_L4, variant, 4) == \
        4 * n * per_elem


def test_peaks_table_names_its_source_and_the_v5e():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9

"""The FLOP and byte functions against hand counts at small shapes."""

import json
from pathlib import Path

import pytest

import harness

BENCH = Path(harness.__file__).resolve().parent


def work(name):
    return harness.load_module(BENCH / "work" / f"{name}.py")


def test_masked_matmul_cost_and_calls():
    mm = work("masked_matmul")
    # 2x3 @ 3x4, fp32: 2*2*3*4 flops; 6 + 12 + 8 elements of 4 bytes
    assert mm.cost(2, 3, 4) == (48.0, (6 + 12 + 8) * 4.0)
    assert mm.cost(2, 3, 4, a_bytes=2, b_bytes=2, out_bytes=4) == (48.0, 6 * 2 + 12 * 2 + 8 * 4.0)
    assert mm.calls_of(2, 3, 4, forward=2) == [(2, 3, 4), (2, 3, 4), (2, 4, 3), (3, 2, 4)]
    assert mm.calls_of(2, 3, 4, backward=False) == [(2, 3, 4)]


def test_ssd_scan_cost_by_hand():
    ssd = work("ssd_scan")
    # batch 1, seq 4 in one chunk of 4, 2 heads, P=2, N=3: 2 grid steps of
    # 2*16*3 + 2*16*2 + 4*4*3*2 = 96 + 64 + 96 flops
    flops, nbytes = ssd.cost(1, 4, 2, 2, 3, chunk=4)
    assert flops == 2 * 256
    # per step: x and y 2*4*2*4, decay 3*4*4, B and C 2*3*4*4
    assert nbytes == 2 * (64 + 48 + 96)
    # a sequence that is not a multiple of the chunk is padded up
    assert ssd.cost(1, 5, 2, 2, 3, chunk=4)[0] == 2 * flops


def test_mamba2_model_flops_by_hand():
    m = work("mamba2-780m")
    cfg = {"d_model": 4, "n_layer": 2, "vocab_size": 10,
           "ssm_cfg": {"d_state": 3, "d_conv": 4, "expand": 2, "headdim": 4, "ngroups": 1}}
    # d_inner 8, 2 heads, proj 2*8 + 2*3 + 2 = 24, conv dim 14
    matmul = 2 * 2 * (4 * 24 + 8 * 4) + 2 * 4 * 10
    conv = 2 * 2 * 4 * 14
    scan = 4 * 2 * 2 * 3 * 4
    assert m.forward_flops_per_token(cfg) == matmul + conv + scan
    assert m.train_flops_per_token(cfg, 64) == 3 * (matmul + conv + scan)
    assert m.matmuls(cfg, 2, 8) == [(16, 4, 24), (16, 8, 4)] * 2
    assert m.ssd_scans(cfg, 2, 8) == [(2, 8, 2, 4, 3)] * 2


@pytest.mark.parametrize("vocab,pad,rows", [(10, 4, 12), (12, 4, 12), (50277, 16, 50288),
                                            (50277, None, 50277)])
def test_the_head_counts_the_padded_embedding_rows(vocab, pad, rows):
    cfg = {"d_model": 4, "n_layer": 1, "vocab_size": vocab,
           "ssm_cfg": {"d_state": 3, "d_conv": 4, "expand": 2, "headdim": 4, "ngroups": 1}}
    if pad is not None:
        cfg["pad_vocab_size_multiple"] = pad
    assert work("mamba2-780m").sizes(cfg)["vocab"] == rows
    assert harness.load_module(BENCH / "reference" / "mamba2-780m.py").embedding_rows(cfg) == rows


def test_published_sizes_give_the_known_parameter_counts():
    cfg = json.loads((BENCH / "configs" / "mamba2-780m.json").read_text())
    m = work("mamba2-780m")
    # matmul parameters of mamba2-780m: 48 x (1536 x 6448 + 3072 x 1536) + tied
    # head over the 50277 tokens padded to a multiple of 16
    z = m.sizes(cfg)
    assert z["proj"] == 6448 and z["heads"] == 48
    matmul = 2 * (48 * (1536 * 6448 + 3072 * 1536) + 1536 * 50288)
    conv = 2 * 48 * 4 * (3072 + 2 * 128)
    scan = 4 * 48 * 48 * 128 * 64
    assert m.forward_flops_per_token(cfg) == matmul + conv + scan
    # the scan's share of a forward token is about 5%
    assert scan / (matmul + conv + scan) == pytest.approx(0.046, abs=0.005)
